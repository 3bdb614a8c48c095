"""Packed-domain server optimization: FedAC / server momentum as fused
finalize-side kernels — cut ROUNDS, not just round time.

The comms campaign (packed codec, streaming folds, ring, compressed
domain, hierarchy) optimized seconds-per-round; this module attacks the
other factor of time-to-accuracy: the NUMBER of communication rounds.
"Federated Accelerated Stochastic Gradient Descent" (FedAC, Yuan & Ma
2020) provably reaches a target loss in fewer rounds than plain FedAvg
by treating the round aggregate as a pseudo-gradient and running an
accelerated server recurrence over it.  The legacy
:mod:`rayfed_tpu.fl.fedopt` optimizers already do the momentum half —
but as per-leaf tree arithmetic over UNPACKED trees, which is why they
were excluded from every packed-domain path (``wire_quant``,
``quorum``, ``mode="ring"/"hierarchy"``).  Here the server step is a
packed-buffer operation living exactly where the aggregation already
lives:

- :class:`PackedServerOpt` — the optimizer *spec* (kind +
  hyperparameters; pure data, hashable, identical on every
  controller).  :func:`server_momentum` builds FedAvgM, :func:`fedac`
  builds FedAC's linear-coupling acceleration ``(λ, γ, β)``:
  conservative step ``y' = x − λ·Δ``, aggressive step ``z' = z − γ·Δ``
  over the auxiliary sequence ``z``, broadcast point
  ``x' = (1−β)·y' + β·z'`` — with ``Δ = x − avg`` the round
  pseudo-gradient.  ``λ=1, β=0`` (or ``momentum=0, lr=1``) reproduces
  plain FedAvg bit-exactly.
- :class:`PackedServerState` — the auxiliary sequence(s) as packed f32
  buffers (one flat buffer per sequence, the same layout the wire
  codec packs), registered as a JAX pytree so it snapshots/restores
  through :class:`rayfed_tpu.checkpoint.FedCheckpointer` like params.
- :class:`PackedServerOptimizer` — one controller's runtime state
  holder.  The step itself (:func:`rayfed_tpu.fl.fedavg.
  server_step_kernel`) runs as ONE fused jitted pass placed beside the
  single finalize: the finalizing node (streaming/quorum coordinator,
  hierarchy root) consumes the EXACT finalized f32 aggregate — the
  donated pass of the composition is the integer fold accumulator
  upstream — and emits the post-step model, which is what
  the downlink ships (quantized rounds re-code the POST-step model via
  the shared :func:`~rayfed_tpu.fl.quantize.quantize_downlink`, so the
  downlink grid is ranged by the post-step delta).  Ring rounds have
  no downlink: every controller already holds the byte-identical
  assembled aggregate and applies the step locally — same kernel, same
  inputs, same bytes.

**State without a state broadcast.**  Every controller replicates the
state, but nobody ships it: after each round the state advances via
:func:`~rayfed_tpu.fl.fedavg.server_resync_kernel` from the broadcast
pair ``(x, x')`` — a deterministic f32 function of buffers the whole
cluster already byte-agrees on.  The coordinator runs the SAME resync
on the decoded broadcast instead of keeping its exact-step state, so
downlink quantization error is absorbed into the state identically
everywhere (momentum becomes "the step the broadcast actually
realized"), every controller can take over as quorum coordinator after
a failover with the right state in hand, and per-party checkpoints of
the state are interchangeable.

**Composition** (enforced by ``fl.trainer.validate_round_config``):
composes with ``wire_quant``, ``streaming_agg``, ``quorum`` (the
cutoff's subset refold reweights the aggregate to the arrived Σw, and
the step consumes exactly that subset mean), ``mode="ring"`` and
``mode="hierarchy"`` (state steps once, at the root, and the tree
broadcast carries the post-step model) and ``checkpointer`` (snapshots
carry the state plus a spec stamp; restoring across differing specs is
refused loudly).  ``overlap=True`` composes too, via the unified
staleness recurrence (``fl/overlap.py`` module docstring): anchoring
the DGA correction ``agg + (w − w_at_send)`` on the POST-step broadcast
makes the step's pseudo-gradient the mean one-round-stale local
displacement — the delayed-gradient regime Federated Accelerated SGD
analyzes — and the pipelined runner drives the identical
``step_fn``/``resync`` pair from its comms lane (bit-exact replay:
``tests/test_overlap.py``).  The buffered asynchronous driver
(``fl/async_rounds.py``) runs the same recurrence at per-party
staleness.  ``secure_agg`` and elastic ``join_ticket`` entry are loud
exclusions (the masked recovery window has not been exercised with a
post-finalize step; welcomes do not carry server-opt state) — never
silent fallbacks.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

# How many auxiliary packed buffers each optimizer kind carries.
_STATE_WIDTH = {"momentum": 1, "fedac": 1}


class PackedServerOpt:
    """A server-optimizer *spec*: kind + static hyperparameters.

    Pure data — every controller constructs an equal spec from the
    same arguments, the kernels cache-compile per spec, and the spec
    stamps checkpoint metadata so a restore across differing specs
    fails loudly instead of silently resetting the trajectory.
    """

    __slots__ = ("kind", "hyper")

    def __init__(self, kind: str, hyper: Sequence[float]) -> None:
        if kind not in _STATE_WIDTH:
            raise ValueError(
                f"unknown server-opt kind {kind!r} — one of "
                f"{sorted(_STATE_WIDTH)}"
            )
        self.kind = str(kind)
        self.hyper = tuple(float(h) for h in hyper)
        if kind == "momentum":
            lr, momentum = self.hyper
            if not lr > 0:
                raise ValueError(f"momentum lr must be > 0, got {lr}")
            if not 0.0 <= momentum < 1.0:
                raise ValueError(
                    f"momentum coefficient must be in [0, 1), got "
                    f"{momentum}"
                )
        else:  # fedac
            lam, gamma, beta = self.hyper
            if not lam > 0:
                raise ValueError(f"fedac lam must be > 0, got {lam}")
            if not gamma >= lam:
                raise ValueError(
                    f"fedac gamma must be >= lam (the aggressive step "
                    f"dominates the conservative one), got gamma="
                    f"{gamma} < lam={lam}"
                )
            if not 0.0 <= beta < 1.0:
                raise ValueError(
                    f"fedac beta must be in [0, 1), got {beta}"
                )

    @property
    def n_state(self) -> int:
        return _STATE_WIDTH[self.kind]

    def init(self, x_buf: Any) -> "PackedServerState":
        """Fresh state for a run starting at packed buffer ``x_buf``:
        momentum starts at zero; FedAC's aggressive sequence starts at
        the initial point (``z₀ = x₀``)."""
        import jax.numpy as jnp

        x = jnp.asarray(np.asarray(x_buf).reshape(-1), jnp.float32)
        if self.kind == "momentum":
            bufs: Tuple[Any, ...] = (jnp.zeros_like(x),)
        else:  # fedac
            bufs = (x,)
        return PackedServerState(self.kind, self.hyper, bufs)

    def describe(self) -> Dict[str, Any]:
        """The JSON-safe spec stamp for checkpoint metadata."""
        return {"kind": self.kind, "hyper": [float(h) for h in self.hyper]}

    def __eq__(self, other: Any) -> bool:
        return (
            isinstance(other, PackedServerOpt)
            and self.kind == other.kind
            and self.hyper == other.hyper
        )

    def __hash__(self) -> int:
        return hash((self.kind, self.hyper))

    def __repr__(self) -> str:  # pragma: no cover
        return f"PackedServerOpt({self.kind!r}, {self.hyper})"


def server_momentum(lr: float = 1.0, momentum: float = 0.9) -> PackedServerOpt:
    """FedAvgM over packed buffers: ``x' = x − lr·(momentum·m + Δ)``.

    ``lr=1, momentum=0`` reproduces plain FedAvg bit-exactly (the step
    kernel returns the aggregate literally in that configuration).
    """
    return PackedServerOpt("momentum", (lr, momentum))


def fedac(lam: float = 1.0, gamma: float = 3.0,
          beta: float = 0.5) -> PackedServerOpt:
    """FedAC (Yuan & Ma 2020) as a server recurrence over packed
    buffers — linear-coupling acceleration of the round
    pseudo-gradient.

    ``lam`` is the conservative (FedAvg-like) step, ``gamma >= lam``
    the aggressive step over the auxiliary sequence, ``beta`` the
    coupling weight of the aggressive sequence in the next broadcast
    point.  ``lam=1, beta=0`` is plain FedAvg bit-exactly; moderate
    ``gamma``/``beta`` provably cut rounds-to-target on smooth
    objectives (``tests/test_server_opt.py`` holds at most 0.8x plain
    FedAvg's rounds to target on the quadratic workload).
    """
    return PackedServerOpt("fedac", (lam, gamma, beta))


class PackedServerState:
    """Server-optimizer auxiliary sequences as packed f32 buffers.

    Registered as a JAX pytree (children = the buffers, aux = the
    spec), so it checkpoints through ``FedCheckpointer`` exactly like
    a params tree and restores structurally via a target built from
    :meth:`PackedServerOpt.init`.
    """

    __slots__ = ("kind", "hyper", "bufs")

    def __init__(self, kind: str, hyper: Tuple[float, ...],
                 bufs: Tuple[Any, ...]) -> None:
        self.kind = str(kind)
        self.hyper = tuple(float(h) for h in hyper)
        self.bufs = tuple(bufs)
        width = _STATE_WIDTH.get(self.kind)
        if width is not None and len(self.bufs) != width:
            raise ValueError(
                f"{self.kind} server-opt state carries {width} "
                f"buffer(s), got {len(self.bufs)}"
            )

    def __repr__(self) -> str:  # pragma: no cover
        sizes = [int(getattr(b, "size", 0)) for b in self.bufs]
        return (
            f"PackedServerState({self.kind!r}, {self.hyper}, "
            f"bufs={sizes})"
        )


import jax  # noqa: E402  (after the numpy-only spec machinery)

jax.tree_util.register_pytree_node(
    PackedServerState,
    lambda s: (tuple(s.bufs), (s.kind, s.hyper)),
    lambda aux, ch: PackedServerState(aux[0], aux[1], tuple(ch)),
)


def describe_server_opt(server_opt: Optional[Any]) -> Dict[str, Any]:
    """The checkpoint-metadata stamp for ANY ``server_opt`` argument:
    ``{"kind": "none"}`` for plain FedAvg, ``{"kind": "fedopt"}`` for a
    legacy :class:`~rayfed_tpu.fl.fedopt.ServerOptimizer` (its
    callables carry no comparable hyperparameters), and the full
    kind+hyper spec for a :class:`PackedServerOpt`.  Single producer —
    the classic and quorum loops stamp and compare exactly this."""
    if server_opt is None:
        return {"kind": "none"}
    if isinstance(server_opt, PackedServerOpt):
        return server_opt.describe()
    return {"kind": "fedopt"}


def check_snapshot_server_opt(stored: Optional[Dict[str, Any]],
                              expected: Dict[str, Any]) -> None:
    """Refuse — loudly, naming both sides — to resume a run whose
    ``server_opt`` config differs from the snapshot's.

    A silent mismatch is the nasty failure mode: restoring a plain-
    FedAvg snapshot into a momentum/FedAC run (or vice versa) resets
    the optimizer trajectory without failing anything — the loss curve
    just quietly degrades.  ``stored=None`` (a snapshot from before
    the stamp existed) is tolerated ONLY for stateless configs
    (``none``/``fedopt`` — exactly the runs old snapshots could have
    come from); a packed run demands the stamp because it also demands
    the state buffers.
    """
    if stored is None:
        if expected["kind"] in ("none", "fedopt"):
            return
        raise ValueError(
            f"checkpoint carries no server_opt stamp (written before "
            f"packed server optimization existed?) but this run uses "
            f"server_opt={expected} — its state buffers cannot be in "
            f"the snapshot; restart from scratch or drop server_opt"
        )
    stored_n = {
        "kind": str(stored.get("kind")),
        **(
            {"hyper": [float(h) for h in stored["hyper"]]}
            if "hyper" in stored else {}
        ),
    }
    if stored_n != expected:
        raise ValueError(
            f"server_opt mismatch between the run and its checkpoint: "
            f"this run is configured with {expected}, the snapshot was "
            f"written by {stored_n} — restoring would silently "
            f"{'reset' if expected['kind'] != 'none' else 'discard'} "
            f"the optimizer trajectory; resume with the matching "
            f"server_opt or point the checkpointer elsewhere"
        )


class PackedServerOptimizer:
    """One controller's server-opt runtime: the replicated state plus
    the step/resync discipline every aggregation topology shares.

    Life cycle per round (all controllers, identical arguments):

    1. ``ensure(x_buf)`` — lazy state init at the round's shared
       starting buffer (first round only).
    2. ``step_fn(x_buf)`` — the finalize-side hook handed to
       ``streaming_aggregate``/``quorum_aggregate``/
       ``hierarchy_aggregate`` (ring/classic paths call it directly on
       the assembled aggregate): ONE fused kernel, exact f32 in, the
       post-step broadcast model out.
    3. ``resync(x_buf, new_buf)`` — after the broadcast landed, every
       controller advances its state replica from the byte-agreed
       ``(x, x')`` pair.  A failed/aborted round never reaches resync,
       so retries and quorum failovers re-run the SAME step from the
       SAME state.
    """

    __slots__ = ("opt", "_state")

    def __init__(self, opt: PackedServerOpt,
                 state: Optional[PackedServerState] = None) -> None:
        if not isinstance(opt, PackedServerOpt):
            raise TypeError(
                f"PackedServerOptimizer wraps a PackedServerOpt spec, "
                f"got {type(opt).__name__} (legacy fedopt.ServerOptimizer "
                f"optimizers keep the unpacked tree path)"
            )
        self.opt = opt
        self._state: Optional[PackedServerState] = None
        if state is not None:
            self.load_state(state)

    @property
    def state(self) -> Optional[PackedServerState]:
        return self._state

    def load_state(self, state: PackedServerState) -> None:
        """Adopt a restored state (checkpoint resume); the spec must
        match — a silently adopted foreign state IS the trajectory
        reset the checkpoint guard exists to prevent."""
        if not isinstance(state, PackedServerState):
            raise TypeError(
                f"expected a PackedServerState, got {type(state).__name__}"
            )
        if (state.kind, state.hyper) != (self.opt.kind, self.opt.hyper):
            raise ValueError(
                f"restored server-opt state was written by "
                f"({state.kind}, {state.hyper}), this run is "
                f"({self.opt.kind}, {self.opt.hyper})"
            )
        self._state = state

    def ensure(self, x_buf: Any) -> None:
        if self._state is None:
            self._state = self.opt.init(x_buf)

    def step_fn(self, x_buf: Any):
        """The round's finalize-side hook: ``fn(aggregate PackedTree)
        -> post-step PackedTree`` (f32 buffer; passthrough leaves pass
        through — momentum over non-float leaves is meaningless, they
        keep the aggregate's per-leaf reduce)."""
        import jax.numpy as jnp

        from rayfed_tpu.fl.fedavg import server_step_kernel

        if self._state is None:
            raise RuntimeError("call ensure(x_buf) before step_fn")
        state = self._state
        x = jnp.asarray(np.asarray(x_buf).reshape(-1), jnp.float32)
        kernel = server_step_kernel(self.opt.kind, self.opt.hyper)

        def _step(result: Any) -> Any:
            from rayfed_tpu.fl.compression import PackedTree, PackSpec
            from rayfed_tpu.fl.quantize import QuantizedPackedTree

            if isinstance(result, QuantizedPackedTree):
                raise TypeError(
                    "the server step consumes the FINALIZED float "
                    "aggregate — got integer codes; apply it between "
                    "finalize and the downlink recode"
                )
            if not isinstance(result, PackedTree):
                raise TypeError(
                    f"the server step consumes a PackedTree aggregate, "
                    f"got {type(result).__name__}"
                )
            n = int(getattr(result.buf, "size", 0))
            if n != int(x.size):
                raise ValueError(
                    f"aggregate has {n} elements, server-opt state "
                    f"covers {int(x.size)} — the round's packed layout "
                    f"changed mid-run"
                )
            buf = kernel(x, jnp.asarray(result.buf), *state.bufs)
            spec = result.spec
            if spec.wire_dtype != "float32":
                spec = PackSpec(spec.entries, spec.treedef, "float32")
            return PackedTree(buf, result.passthrough, spec)

        return _step

    def resync(self, x_buf: Any, new_buf: Any) -> None:
        """Advance the state replica from the round's byte-agreed
        broadcast pair — every controller calls this with identical
        buffers, so every replica stays byte-identical."""
        import jax.numpy as jnp

        from rayfed_tpu.fl.fedavg import server_resync_kernel

        if self._state is None:
            raise RuntimeError("resync before any round was stepped")
        x = jnp.asarray(np.asarray(x_buf).reshape(-1), jnp.float32)
        new = jnp.asarray(np.asarray(new_buf).reshape(-1), jnp.float32)
        if int(new.size) != int(x.size):
            raise ValueError(
                f"broadcast has {int(new.size)} elements, server-opt "
                f"state covers {int(x.size)}"
            )
        bufs = server_resync_kernel(self.opt.kind, self.opt.hyper)(
            x, new, *self._state.bufs
        )
        self._state = PackedServerState(
            self.opt.kind, self.opt.hyper, tuple(bufs)
        )

    def describe(self) -> Dict[str, Any]:
        return self.opt.describe()


def reference_step(opt: PackedServerOpt, x: np.ndarray, avg: np.ndarray,
                   state: List[np.ndarray]) -> Tuple[np.ndarray, List[np.ndarray]]:
    """Pure-numpy reference of one (step, true-state-update) round —
    what the fused kernels are unit-tested against (tests/bench only;
    the production state advances via the resync kernel instead)."""
    x = np.asarray(x, np.float32)
    avg = np.asarray(avg, np.float32)
    if opt.kind == "momentum":
        lr, momentum = opt.hyper
        m = momentum * state[0] + (x - avg)
        return (x - lr * m).astype(np.float32), [m.astype(np.float32)]
    lam, gamma, beta = opt.hyper
    delta = x - avg
    y_new = x - lam * delta
    z_new = state[0] - gamma * delta
    x_new = (1.0 - beta) * y_new + beta * z_new
    return x_new.astype(np.float32), [z_new.astype(np.float32)]
