"""Pipelined federated rounds: hide cross-party aggregation under compute.

The synchronous round loop serializes its two expensive phases: every
party finishes its local steps, pushes its delta, then **idles** until
the aggregate comes back — per-round wall time is ``compute + comms``
even though the two use disjoint resources (devices vs the wire).  After
the codec (PR 1), the receive path (PR 2) and the topology (PR 3)
squeezed the comms term itself, the remaining cost is that
serialization.

This module removes it with **one round of bounded staleness**
(delayed-gradient averaging — Federated Accelerated SGD,
arXiv:2006.08950; transparent-overlap proxies, arXiv:2305.09593): after
computing its round-*k* model, each party hands the push + aggregation
of round *k* to a background **comms lane**
(:class:`rayfed_tpu.executor.CommsLane`) and immediately begins round
*k+1* local steps from its *locally updated* model.  When the round-*k*
aggregate lands, the party folds it in with the DGA correction::

    w  ←  agg_k + (w_local − w_local_at_send)

i.e. the delayed global average replaces the stale local base while the
local progress made meanwhile is preserved verbatim.  Writing
``Δ_{k+1,p}`` for party *p*'s round-*k+1* local progress, the global
model evolves as ``agg_{k+1} = agg_k + mean_p Δ_{k+1,p}`` — exactly the
synchronous FedAvg recurrence except that each ``Δ`` is computed from a
one-round-stale base.  Per-round wall time drops from
``compute + comms`` to ``max(compute, comms)`` (+ the cheap correction).

Multi-controller determinism: every controller runs the identical main-
thread program (train → correct → hand off), so the fed seq-id streams
stay aligned; the lane NEVER allocates seq ids — each round's
aggregation ids are drawn on the main thread in program order and passed
in (``seq_ids=``), because an off-thread ``next_seq_id`` would
interleave nondeterministically with task ids and desync the rendezvous.

Fault story: every in-flight round is tagged with its round index (the
frames carry ``wire.ROUND_TAG_KEY``), and a ring round whose
aggregation aborts is **re-aggregated — same round, same
contributions — over the coordinator topology** before the runner
moves on: the abort (:class:`~rayfed_tpu.fl.ring.RingRoundError`,
peer death included) surfaces on every controller (poison cascade +
commit ring), so all of them take the fallback in lockstep, mirroring
the synchronous driver's ring→coordinator contract.  Coordinator-mode
failures propagate loudly on every controller instead of falling back
(a rerun over the same topology with the same contributions would fail
identically) — either way a round is never silently skipped.

``run_fedavg_rounds(overlap=True)`` is the one-call entry point;
:class:`PipelinedRoundRunner` is the engine underneath for callers that
want to drive rounds themselves.

**The unified staleness recurrence** (ROADMAP item 1a, shipped here) is
what lets the correction compose with delta-grid coding
(``wire_quant``) and with the accelerated server step (``server_opt``)
— both were loud exclusions until the following two observations:

*Overlap x wire_quant.*  Write ``b_{k-1}`` for the round-(k−1)
broadcast (the value every controller byte-agrees on).  Round *k*'s
corrected contribution is ``c_p = b_{k-1} + (u_p − c_p^{prev})``, so
its delta against the round's shared reference — which IS ``b_{k-1}``,
exactly as in the synchronous quantized loop — is::

    c_p − b_{k-1}  =  u_p − c_p^{prev}

i.e. the party's *local displacement over one round of training*: the
same quantity whose scale the synchronous loop's delta grid is ranged
for (previous aggregate delta x ``QUANT_DELTA_EXPAND`` headroom).  The
DGA correction therefore **commutes with delta-grid coding**: quantize
the corrected contribution against the broadcast reference and you have
coded the raw displacement, bit for bit (``dga_correct`` computes in
f32 and casts once to the wire dtype, so no intermediate rounding
intrudes).  The runner derives the round grid from the previous
broadcast delta — the identical shared-buffer derivation as
``run_fedavg_rounds``'s classic loop — with round 0 unquantized
(bootstrap, nothing observed yet), and hands ``quant/quant_ref/
quant_scope`` to the very same collective codepaths
(``streaming_aggregate`` / ``ring_aggregate``), RoundCodec EF
discipline included.

*Overlap x server_opt.*  With a packed server step the broadcast is
``b_k = step(x_k, m_k)`` where ``m_k = mean_p c_p`` is the finalized
mean.  Anchor the correction on that post-step broadcast —
``c_p ← b_{k-1} + (u_p − c_p^{prev})``, literally the same
``dga_correct`` call — and take means::

    m_k − b_{k-1}  =  mean_p u_p − m_{k-1}

The step's pseudo-gradient ``x_k − m_k`` therefore consumes exactly the
**mean one-round-stale local displacement**: the accelerated recurrence
runs on delayed gradients (the delayed-gradient regime Federated
Accelerated SGD analyzes, arXiv:2006.08950) instead of silently
composing ``step(x, agg) + Δ`` as a naive pairing would.  Mechanically
the runner passes the finalize-side step hook into the collective (the
coordinator steps the exact finalized f32 once; ring rounds step the
byte-identical assembly locally on every controller) and resyncs the
replicated optimizer state from each landed broadcast pair — the same
state-without-a-state-broadcast contract as every synchronous topology
(fl.server_opt).  Both compositions are verified bit-exactly by
in-process replays in ``tests/test_overlap.py`` (see the composition
matrix rows).  (The QUORUM loop's straggler late fold — the same
``dga_correct`` call — composes the same way one level down: the missed
contribution reaches the optimizer one round late inside the NEXT
round's pseudo-gradient; see ``docs/source/server_optimization.rst``.)
This recurrence is also the prerequisite the buffered asynchronous
driver builds on — ``fl/async_rounds.py`` runs it at per-party
staleness instead of the uniform one-round lag.
"""

from __future__ import annotations

import functools
import logging
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

logger = logging.getLogger(__name__)


@functools.lru_cache(maxsize=None)
def _dga_kernel(out_dtype_name: str):
    """One fused ``agg + (cur − base)`` over packed wire buffers.

    All three operands convert to f32 for the arithmetic (the wire dtype
    is usually bf16 — subtracting near-equal bf16 values directly would
    lose the low bits the correction exists to preserve) and the result
    casts back to the wire dtype in the same fused program.
    """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def _corr(agg, cur, base):
        return (
            agg.astype(jnp.float32)
            + cur.astype(jnp.float32)
            - base.astype(jnp.float32)
        ).astype(jnp.dtype(out_dtype_name))

    return _corr


def dga_correct(agg: Any, cur: Any, base: Any) -> Any:
    """``agg + (cur − base)`` on PackedTrees — the DGA staleness fix.

    ``agg`` is the delayed round aggregate, ``cur`` the party's current
    local model, ``base`` the local model at the time its contribution
    was sent (= what ``cur`` was trained from).  Runs as a party-local
    fed task inside the pipelined loop; exposed for tests and custom
    runners.  Non-float (passthrough) leaves get the same elementwise
    recurrence.
    """
    from rayfed_tpu.fl.compression import PackedTree

    for name, tree in (("agg", agg), ("cur", cur), ("base", base)):
        if not isinstance(tree, PackedTree):
            raise TypeError(
                f"dga_correct consumes PackedTrees; {name} is "
                f"{type(tree).__name__} — trainers must return "
                "fl.compress(updated, packed=True)"
            )
    if cur.spec != base.spec:
        raise ValueError(
            "dga_correct: cur/base pack specs differ — the trainer "
            "changed its tree structure mid-run"
        )
    if (
        agg.spec.entries != cur.spec.entries
        or agg.spec.treedef != cur.spec.treedef
    ):
        raise ValueError(
            "dga_correct: aggregate pack spec differs from the local "
            "model's — all parties must pack the identical structure"
        )
    buf = _dga_kernel(cur.spec.wire_dtype)(agg.buf, cur.buf, base.buf)
    passthrough = tuple(
        a + (c - b)
        for a, c, b in zip(agg.passthrough, cur.passthrough, base.passthrough)
    )
    return PackedTree(buf, passthrough, cur.spec)


class _InFlight:
    """One round's aggregation handed to the comms lane."""

    __slots__ = ("round_index", "ref", "rec")

    def __init__(self, round_index: int, ref: Any, rec: Dict[str, float]):
        self.round_index = round_index
        self.ref = ref
        self.rec = rec


class PipelinedRoundRunner:
    """Double-buffered FedAvg rounds: round *k*'s comms under round
    *k+1*'s compute.

    ``trainers``/``weights``/``mode``/``coordinator`` as in
    :func:`rayfed_tpu.fl.run_fedavg_rounds`; the trainer wire contract
    is the packed one (``train`` decompresses its argument and returns
    ``fl.compress(updated, packed=True)``).  ``mode="coordinator"``
    aggregates each round with
    :func:`~rayfed_tpu.fl.streaming.streaming_aggregate` (delta streams
    + on-the-wire folding); ``mode="ring"`` with
    :func:`~rayfed_tpu.fl.ring.ring_aggregate`, falling back to the
    coordinator topology for any round the ring aborts — both compose
    with the overlap because the lane only needs a blocking collective
    call with pre-allocated seq ids.

    ``wire_quant``: optional integer wire dtype name (``"uint8"`` /
    ``"uint16"``) — rounds run compressed-domain exactly like the
    synchronous quantized loop (delta grid derived from the previous
    broadcast delta, round 0 unquantized bootstrap, scoped
    error-feedback residual under ``stream``); the unified staleness
    recurrence (module docstring) is why the corrected contribution
    codes exactly.  ``server_opt``: optional packed server optimizer
    (:class:`~rayfed_tpu.fl.server_opt.PackedServerOptimizer`, or the
    bare packed spec, which gets wrapped) — the broadcast becomes the
    post-step model and the step consumes the mean one-round-stale
    local displacement as its pseudo-gradient.

    Every controller constructs the runner with identical arguments and
    calls :meth:`run` at the same program point (the usual
    multi-controller contract).
    """

    def __init__(
        self,
        trainers: Dict[str, Any],
        *,
        weights: Optional[Sequence[float]] = None,
        mode: str = "coordinator",
        coordinator: Optional[str] = None,
        wire_dtype: Any = None,
        stream: str = "fedavg",
        on_round: Optional[Callable[[int, Any], None]] = None,
        ring_chunk_elems: Optional[int] = None,
        wire_quant: Optional[str] = None,
        server_opt: Any = None,
    ) -> None:
        if not trainers:
            raise ValueError("PipelinedRoundRunner needs trainers")
        if mode not in ("coordinator", "ring"):
            raise ValueError(
                f"unknown mode {mode!r}: expected 'coordinator' or 'ring'"
            )
        if weights is not None and len(weights) != len(trainers):
            raise ValueError(
                f"{len(weights)} weights for {len(trainers)} trainers"
            )
        if coordinator is not None and coordinator not in trainers:
            raise ValueError(
                f"coordinator {coordinator!r} is not a training party "
                f"({sorted(trainers)})"
            )
        self._trainers = trainers
        self._weights = (
            None if weights is None else [float(w) for w in weights]
        )
        self._mode = mode
        self._coord = coordinator if coordinator is not None else min(trainers)
        import jax.numpy as jnp

        self._wire_dtype = jnp.bfloat16 if wire_dtype is None else wire_dtype
        self._stream = stream
        self._on_round = on_round
        self._ring_chunk_elems = ring_chunk_elems
        self._wire_quant = None if wire_quant is None else str(wire_quant)
        if server_opt is not None and not hasattr(server_opt, "step_fn"):
            # Convenience for direct-runner callers: accept the bare
            # packed spec and wrap it the way run_fedavg_rounds does.
            from rayfed_tpu.fl.server_opt import PackedServerOptimizer

            server_opt = PackedServerOptimizer(server_opt)
        self._sopt = server_opt
        # The local controller's party — set by run() (the runtime is
        # not required at construction time); stamps the flight
        # recorder's driver.round / overlap.hidden spans.
        self._me: Optional[str] = None

    # -- lane-side: one round's push + aggregate (+ fallback) ----------------

    def _aggregate_round(
        self,
        r: int,
        objs: List[Any],
        seq_ids: Sequence[int],
        fallback_ids: Sequence[int],
        rec: Dict[str, float],
        grid: Any = None,
        ref: Any = None,
        step_fn: Optional[Callable[[Any], Any]] = None,
    ) -> Any:
        from rayfed_tpu.fl.ring import RING_STATS, RingRoundError, ring_aggregate
        from rayfed_tpu.fl.streaming import streaming_aggregate

        # Under a server step the aggregate must come back f32 (the
        # step's pseudo-gradient lives below bf16 resolution); quant
        # rounds finalize f32 already.
        out_dtype = "float32" if step_fn is not None else None
        t0 = time.perf_counter()
        try:
            if self._mode != "ring":
                # No fallback on the coordinator topology: its failures
                # (poisoned contribution, dead peer) would fail a rerun
                # over the SAME topology with the SAME contributions
                # identically, and a coordinator-side timeout doesn't
                # reach the participants as a catchable error — a
                # fallback here would desync the controllers.  The
                # error surfaces loudly on every controller instead
                # (result poison); the round is never silently skipped.
                return streaming_aggregate(
                    objs, self._weights, stream=self._stream,
                    coordinator=self._coord, seq_ids=seq_ids,
                    round_tag=r, timings=rec,
                    out_dtype=out_dtype,
                    quant=grid, quant_ref=ref,
                    quant_scope=self._stream if grid is not None else None,
                    # Quantize the result broadcast too — the downlink
                    # is the other half of the round's bytes (same as
                    # the synchronous quantized loop).
                    quant_downlink=grid is not None,
                    server_step=step_fn,
                )
            try:
                agg = ring_aggregate(
                    objs, self._weights, stream=self._stream,
                    chunk_elems=self._ring_chunk_elems,
                    seq_ids=seq_ids, round_tag=r, timings=rec,
                    out_dtype=out_dtype,
                    quant=grid, quant_ref=ref,
                    quant_scope=self._stream if grid is not None else None,
                )
                if step_fn is not None:
                    # The ring has no downlink — every controller holds
                    # the byte-identical assembled aggregate, so each
                    # applies the same deterministic f32 step locally
                    # and all byte-agree on the post-step model.
                    agg = step_fn(agg)
                return agg
            except RingRoundError as exc:
                # The abort reached every controller (poison cascade +
                # commit ring — ring_aggregate's contract, peer death
                # included), so all of them take this branch in
                # lockstep: re-aggregate the SAME round's contributions
                # over the coordinator topology — the owners still hold
                # them, so no training work is lost and no round is
                # silently skipped.  Only a failed fallback propagates.
                # Mirrors the synchronous driver's ring→coordinator
                # contract.
                logger.warning(
                    "pipelined round %d ring aggregation failed (%s); "
                    "re-aggregating the same round synchronously over "
                    "the coordinator topology at %r", r, exc, self._coord,
                )
                RING_STATS["fallback_rounds"] += 1
                return streaming_aggregate(
                    objs, self._weights, stream=self._stream,
                    coordinator=self._coord, seq_ids=fallback_ids,
                    round_tag=r, timings=rec,
                    out_dtype=out_dtype,
                    # Same grid, same (uncommitted) residual: the
                    # fallback re-quantizes the identical codes the ring
                    # round would have folded.  Downlink stays plain —
                    # recovery path, keep it simple.  The server step
                    # re-runs from the same (never-resynced) state.
                    quant=grid, quant_ref=ref,
                    quant_scope=self._stream if grid is not None else None,
                    server_step=step_fn,
                )
        finally:
            # Raw lane window (fallback included).  The lane job BLOCKS
            # on this party's own contribution before any byte can move,
            # so the honest comms wall is computed in _collect from
            # [contribution ready → aggregate landed], not from here.
            rec["_lane_t0"] = t0
            rec["_lane_t1"] = time.perf_counter()

    # -- main-thread driver ---------------------------------------------------

    def _alloc_ids(self, runtime) -> tuple:
        """Draw the round's aggregation seq ids in main-thread program
        order — primary ids for the mode's collective, plus fallback ids
        for the same-round synchronous re-aggregation.  Allocated
        unconditionally (used or not) so every controller's counter
        advances identically."""
        from rayfed_tpu.fl.ring import RING_SEQ_IDS
        from rayfed_tpu.fl.streaming import STREAM_AGG_SEQ_IDS

        n = RING_SEQ_IDS if self._mode == "ring" else STREAM_AGG_SEQ_IDS
        primary = tuple(runtime.next_seq_id() for _ in range(n))
        fallback = tuple(
            runtime.next_seq_id() for _ in range(STREAM_AGG_SEQ_IDS)
        )
        return primary, fallback

    def _collect(
        self,
        inflight: _InFlight,
        backstop: float,
        next_u_done: Optional[Dict[str, Any]] = None,
    ) -> Any:
        """Block until the in-flight round's aggregate lands; rewrites
        the round's record with the HONEST comms wall.

        The lane job blocks on this party's own contribution before any
        byte can move, so the raw call walls (``push_s``/``agg_s`` as
        measured inside the collective) include local-compute wait.  The
        true comms window runs from [contribution ready → aggregate
        landed].  ``hidden_s`` is the share of that window spent under
        the NEXT round's local train (``next_u_done`` holds its
        completion timestamp; the train starts from the same event that
        opens the comms window — the corrected contribution — so the
        hidden stretch is [window start → min(window end, train end)]).
        The main thread's own blocked time is NOT the measure: training
        runs on the task pool, so in steady state the main thread sits
        in this wait for the whole round period whether or not comms
        overlapped anything.  Only one round of compute can hide a
        round's comms — round *k+2*'s train consumes the round-*k+1*
        correction, which consumes this very aggregate.
        """
        agg = inflight.ref.resolve(timeout=backstop)
        rec = inflight.rec
        t_round0 = rec.pop("_t0", None)
        lane_t0 = rec.pop("_lane_t0", None)
        lane_t1 = rec.pop("_lane_t1", None)
        start = None
        if lane_t0 is not None and lane_t1 is not None:
            # My contribution resolved before the aggregate could land,
            # so the local_s callback has fired by now.  The window can
            # also not open before the (serial) lane reached this job.
            ready = (
                t_round0 + rec["local_s"]
                if t_round0 is not None and rec["local_s"] > 0.0
                else lane_t0
            )
            start = max(ready, lane_t0)
            # The collective measured its walls from its OWN call start;
            # anchor them on the absolute lane end to stay correct even
            # when a fallback re-aggregation overwrote the record.
            t_call0 = lane_t1 - rec["agg_s"] if rec["agg_s"] > 0.0 else start
            rec["push_s"] = max(0.0, t_call0 + rec["push_s"] - start)
            rec["agg_s"] = max(0.0, lane_t1 - start)
            if next_u_done is not None:
                # A next-round train still running at this landing has
                # covered the whole window (it cannot have started
                # after ``start`` opened the window).
                done = next_u_done.get("t")
                end_hidden = (
                    lane_t1 if done is None else min(lane_t1, done)
                )
                rec["hidden_s"] = min(
                    max(0.0, end_hidden - start), rec["agg_s"]
                )
        logger.debug(
            "round %d timings: local=%.3fs push=%.3fs agg=%.3fs "
            "hidden=%.3fs",
            inflight.round_index, rec.get("local_s", 0.0),
            rec.get("push_s", 0.0), rec.get("agg_s", 0.0),
            rec["hidden_s"],
        )
        from rayfed_tpu import telemetry as _telemetry

        _tr = _telemetry.active()
        if _tr is not None and lane_t1 is not None:
            # The honest round record as a span, plus the overlap's
            # hidden-comms window — the stretch of round k's comms that
            # ran UNDER round k+1's train.  Wall anchors derive from
            # the perf-counter marks relative to now (the ring append
            # itself never blocks the lane).
            now_p, now_w = time.perf_counter(), time.time()
            anchor = t_round0 if t_round0 is not None else lane_t0
            _tr.emit(
                "driver.round", round=inflight.round_index,
                party=self._me, peer=self._coord,
                t_start=now_w - (now_p - anchor),
                dur_s=max(0.0, lane_t1 - anchor),
                detail={
                    k: (round(v, 6) if isinstance(v, float) else v)
                    for k, v in rec.items()
                },
            )
            if start is not None and rec["hidden_s"] > 0.0:
                _tr.emit(
                    "overlap.hidden", round=inflight.round_index,
                    party=self._me,
                    t_start=now_w - (now_p - start),
                    dur_s=rec["hidden_s"],
                    detail={"agg_s": round(rec["agg_s"], 6)},
                )
        return agg

    def run(
        self,
        params: Any,
        rounds: int,
        *,
        timings: Optional[List[Dict[str, float]]] = None,
    ) -> Any:
        """Run ``rounds`` pipelined rounds from ``params``; returns the
        final global params (a decompressed tree, identical on every
        controller up to the one-round staleness semantics).

        ``timings``: optional list receiving one
        ``{"local_s", "push_s", "agg_s", "hidden_s"}`` dict per round
        (also logged at debug level as each round's aggregate lands).
        """
        if rounds < 1:
            raise ValueError(f"rounds must be >= 1, got {rounds}")
        import rayfed_tpu as fed
        from rayfed_tpu.executor import CommsLane
        from rayfed_tpu.fl.compression import compress, decompress
        from rayfed_tpu.runtime import get_runtime

        runtime = get_runtime()
        me = runtime.party
        self._me = me
        backstop = runtime.job_config.recv_backstop_s
        parties = list(self._trainers)
        outgoing = compress(params, packed=True, wire_dtype=self._wire_dtype)
        # Compressed-domain / server-opt round state (the unified
        # staleness recurrence, module docstring).  ``round_base`` is
        # the f32 reference every controller byte-agrees on for the
        # round about to be SUBMITTED (round 0: the f32 pack of the
        # init; later: the f32 view of the latest landed broadcast);
        # ``inflight_base`` anchors the IN-FLIGHT round's step/grid so
        # the optimizer resync and the next grid derivation use the
        # matching broadcast pair when that round lands.
        sopt = self._sopt
        use_quant = self._wire_quant is not None
        round_base = None
        inflight_base = None
        prev_delta = None
        if use_quant or sopt is not None:
            import jax.numpy as jnp
            import numpy as _np

            from rayfed_tpu.fl.compression import pack_tree

            round_base = _np.asarray(pack_tree(params, jnp.float32).buf)
        lane = CommsLane(
            name=f"rayfed-comms-{me}",
            bind_runtime_fn=runtime.bind_thread,
        )
        try:
            inputs: Dict[str, Any] = {p: outgoing for p in parties}
            prev_contribs: Optional[Dict[str, Any]] = None
            inflight: Optional[_InFlight] = None
            for r in range(rounds):
                rec: Dict[str, Any] = {
                    "local_s": 0.0, "push_s": 0.0, "agg_s": 0.0,
                    "hidden_s": 0.0,
                    # Correlation stamp (flight recorder): the same
                    # keys the transport rides on every frame, so this
                    # row joins the wire's view of its round.  The
                    # overlap runner has no roster epoch.
                    "round": r, "epoch": None, "coordinator": self._coord,
                }
                t_r0 = time.perf_counter()
                rec["_t0"] = t_r0  # popped by _collect
                # Round-r local steps — each party trains from its OWN
                # model (round 0: the shared init; later: its corrected
                # model), so launching costs no wire traffic and no
                # barrier.
                u = {
                    p: self._trainers[p].train.remote(inputs[p])
                    for p in parties
                }
                # Absolute end of MY round-r train — _collect uses it to
                # measure how much of round r-1's comms window this
                # train covered (hidden_s).
                u_done: Optional[Dict[str, Any]] = None
                if me in u:
                    u_ref = u[me].get_local_ref()
                    if u_ref is not None:
                        u_done = {"t": None}
                        u_ref.add_done_callback(
                            lambda _ref, d=u_done: d.__setitem__(
                                "t", time.perf_counter()
                            )
                        )
                if inflight is None:
                    contribs = u  # round 0: raw local models
                else:
                    # Round r-1's aggregate lands here — usually already
                    # done (it ran under round r-1→r compute); apply the
                    # DGA correction as a party-local fed task chained
                    # on the round-r train output.
                    agg_prev = self._collect(inflight, backstop, u_done)
                    if use_quant or sopt is not None:
                        new_base = _np.asarray(agg_prev.buf).astype(
                            _np.float32
                        )
                        if sopt is not None:
                            # Every controller advances its state
                            # replica from the landed round's
                            # byte-agreed broadcast pair — zero extra
                            # wire bytes (fl.server_opt).
                            sopt.resync(
                                inflight_base, _np.asarray(agg_prev.buf)
                            )
                        if use_quant:
                            # What the grid must cover next round: how
                            # far the global model just moved (under
                            # server_opt: the POST-step delta).
                            prev_delta = new_base - round_base
                        round_base = new_base
                    if self._on_round is not None:
                        self._on_round(
                            inflight.round_index, decompress(agg_prev)
                        )
                    contribs = {
                        p: fed.remote(dga_correct).party(p).remote(
                            agg_prev, u[p], prev_contribs[p]
                        )
                        for p in parties
                    }
                if me in contribs:
                    local_ref = contribs[me].get_local_ref()
                    if local_ref is not None:
                        local_ref.add_done_callback(
                            lambda _ref, rec=rec, t0=t_r0: rec.__setitem__(
                                "local_s", time.perf_counter() - t0
                            )
                        )
                # Round-r grid/step, derived from broadcast values only
                # (bit-identical on every controller).  The FIRST round
                # has no observed delta yet and runs unquantized
                # (bootstrap) — exactly the synchronous quantized loop.
                round_grid = None
                if use_quant and prev_delta is not None:
                    from rayfed_tpu.fl import quantize as _qz

                    round_grid = _qz.make_round_grid(
                        prev_delta, wire_dtype=self._wire_quant,
                        mode="delta",
                        # The grid chunking must BE the ring's stripe
                        # chunking, or ring_aggregate's chunk-match
                        # guard would abort (and fall back) every
                        # quantized round.
                        chunk_elems=(
                            self._ring_chunk_elems
                            if self._mode == "ring" else None
                        ),
                        # Per-party deltas overshoot the aggregate
                        # delta; what still clips rides the EF
                        # residual.
                        expand=_qz.QUANT_DELTA_EXPAND,
                    )
                step_fn = None
                if sopt is not None:
                    sopt.ensure(round_base)
                    step_fn = sopt.step_fn(round_base)
                inflight_base = round_base
                seq_ids, fallback_ids = self._alloc_ids(runtime)
                inflight = _InFlight(
                    r,
                    lane.submit(
                        self._aggregate_round, r, list(contribs.values()),
                        seq_ids, fallback_ids, rec,
                        round_grid,
                        round_base if use_quant else None,
                        step_fn,
                    ),
                    rec,
                )
                if timings is not None:
                    timings.append(rec)
                # Round r+1 trains from the corrected round-r model —
                # which IS the round-r contribution (the correction both
                # fixes the contribution and advances the local model).
                prev_contribs = contribs
                inputs = contribs
            final = self._collect(inflight, backstop)
            if self._on_round is not None:
                self._on_round(rounds - 1, decompress(final))
            return decompress(final)
        finally:
            lane.shutdown(wait=False)
