"""High-level federated training driver: the round loop as one call.

The reference leaves the round loop to user code (its canonical shape
is the hand-rolled loop in its ``tests/test_fed_get.py:47-82``); here
the loop is a first-class driver that composes the framework's pieces —
coordinator aggregation with pipelined (lazy) rounds, FedOpt server
optimizers, bf16 wire compression, and per-party checkpoint/resume —
while preserving the multi-controller contract: every party calls
:func:`run_fedavg_rounds` at the same program point with the same
arguments and walks the identical seq-id sequence.

Checkpoint/resume: with a ``checkpointer``, each party snapshots
``(round, params, server-opt state)`` every ``checkpoint_every`` rounds
and the NEXT call resumes from the latest complete snapshot — restart
all parties and the loop continues where it left off (deterministic
seq-ids re-align the rendezvous, SURVEY §5.4's resume story).
"""

from __future__ import annotations

import logging
from typing import Any, Callable, Optional, Sequence

from rayfed_tpu.fl.compression import ErrorFeedback, compress, decompress
from rayfed_tpu.fl.fedavg import aggregate
from rayfed_tpu.fl.fedopt import ServerOptimizer

logger = logging.getLogger(__name__)

# Headroom factor for compressed-domain uplink grids (wire_quant) —
# shared with the quorum driver loop so both derive bit-identical grids
# (see fl.quantize.QUANT_DELTA_EXPAND for the rationale).
from rayfed_tpu.fl.quantize import QUANT_DELTA_EXPAND as _QUANT_DELTA_EXPAND


def sample_parties(
    parties: Sequence[str], sample: int, sample_seed: int, round_index: int
) -> list:
    """The per-round participation draw, shared by every controller.

    Draws from the **sorted** party list: the population order must be
    canonical, not dict insertion order — two controllers that built
    their ``trainers`` mapping in different orders would otherwise draw
    DIFFERENT subsets from the identical seed (``rng.sample`` picks by
    index), desyncing the seq-id streams into a hang.  The result is
    sorted too, so coordinator choice is order-stable.
    """
    import random as _random

    rng = _random.Random(int(sample_seed) * 1_000_003 + round_index)
    return sorted(rng.sample(sorted(parties), int(sample)))


def validate_round_config(
    trainers: dict,
    *,
    rounds: int = 1,
    server_opt: Optional[Any] = None,
    weights: Optional[Sequence[float]] = None,
    compress_wire: bool = False,
    packed_wire: bool = False,
    checkpointer: Any = None,
    checkpoint_every: int = 0,
    sample: Optional[int] = None,
    aggregator: Optional[Callable[[Sequence[Any]], Any]] = None,
    streaming_agg: bool = False,
    error_feedback: bool = False,
    wire_quant: Optional[Any] = None,
    mode: str = "coordinator",
    coordinator: Optional[str] = None,
    overlap: bool = False,
    ring_chunk_elems: Optional[int] = None,
    region_size: Optional[int] = None,
    region_branch: Optional[int] = None,
    region_quorum: Optional[int] = None,
    region_deadline_s: Optional[float] = None,
    quorum: Optional[int] = None,
    round_deadline_s: Optional[float] = None,
    join_ticket: Optional[dict] = None,
    round_log: Optional[list] = None,
    secure_agg: bool = False,
) -> dict:
    """Validate one round-loop configuration WITHOUT running it.

    The single producer of every feature-composition verdict
    :func:`run_fedavg_rounds` enforces: each feature pair either
    passes here (and is exercised bit-exactly by a test or bench gate)
    or raises a LOUD ``ValueError`` naming the clash — never a silent
    fallback.  Extracted so the composition-matrix test
    (``tests/test_composition_matrix.py``) can drive the full pairwise
    grid in-process, with no runtime or party subprocesses.

    Returns the normalized bits the driver needs downstream:
    ``{"wire_quant": <dtype name or None>, "checkpoint_every": <int>,
    "server_opt_kind": "none"|"fedopt"|"packed"}``.
    """
    from rayfed_tpu.fl.server_opt import PackedServerOpt

    packed_opt = (
        server_opt if isinstance(server_opt, PackedServerOpt) else None
    )
    legacy_opt = (
        server_opt
        if (server_opt is not None and packed_opt is None)
        else None
    )
    if legacy_opt is not None and not isinstance(
        legacy_opt, ServerOptimizer
    ):
        raise ValueError(
            f"server_opt must be a fl.server_opt.PackedServerOpt "
            f"(packed-domain momentum/FedAC — composes with "
            f"wire_quant/quorum/ring/hierarchy) or a legacy "
            f"fl.fedopt.ServerOptimizer, got "
            f"{type(server_opt).__name__}"
        )
    if rounds < 1:
        raise ValueError(f"rounds must be >= 1, got {rounds}")
    if checkpoint_every and checkpointer is None:
        raise ValueError("checkpoint_every set without a checkpointer")
    if checkpoint_every < 0:
        raise ValueError(f"checkpoint_every must be >= 0, got {checkpoint_every}")
    if checkpointer is not None and not checkpoint_every:
        # A checkpointer with checkpoint_every=0 would resume but never
        # save — snapshot every round rather than silently never.
        checkpoint_every = 1
    if aggregator is not None and weights is not None:
        raise ValueError(
            "aggregator and weights are mutually exclusive (a custom "
            "reducer defines its own weighting)"
        )
    if sample is not None and not 1 <= int(sample) <= len(trainers):
        raise ValueError(
            f"sample must be in [1, {len(trainers)}], got {sample}"
        )
    if sample is not None and weights is not None:
        raise ValueError(
            "sample and weights are mutually exclusive (a weight "
            "sequence cannot align with a changing per-round subset)"
        )
    _qname = None
    if wire_quant is not None:
        import numpy as _np

        _qname = _np.dtype(wire_quant).name
        if _qname not in ("uint8", "int8"):
            raise ValueError(
                f"wire_quant must be an 8-bit integer dtype (uint8/"
                f"int8), got {_qname!r}"
            )
        if not (compress_wire and packed_wire):
            raise ValueError(
                "wire_quant requires compress_wire=True and "
                "packed_wire=True (the quantized unit is the packed "
                "wire buffer)"
            )
        if (
            not streaming_agg
            and mode not in ("ring", "hierarchy")
            and quorum is None
        ):
            raise ValueError(
                "wire_quant requires streaming_agg=True, mode='ring', "
                "mode='hierarchy' or quorum= — the compressed-domain "
                "fold lives in the streaming/striped aggregators "
                "(fl.quantize)"
            )
        incompat_q = {
            "error_feedback": error_feedback,  # quant carries its OWN
            "aggregator": aggregator is not None,
            # PACKED server optimizers (fl.server_opt) compose: the
            # step runs on the exact finalized f32 beside the single
            # rescale.  Only the legacy per-leaf tree optimizers are
            # excluded here.  overlap=True composes too (the unified
            # staleness recurrence, fl.overlap): the DGA-corrected
            # contribution's delta against the round's shared broadcast
            # reference is exactly the party's local displacement, so
            # delta-grid coding commutes with the correction.
            "server_opt": legacy_opt is not None,
        }
        bad_q = [k for k, v in incompat_q.items() if v]
        if bad_q:
            raise ValueError(
                f"wire_quant is incompatible with {bad_q}: the "
                f"grid codec carries its own error feedback, the "
                f"other paths have not been taught the quantized round "
                f"shape, and a legacy fedopt.ServerOptimizer runs "
                f"per-leaf tree arithmetic — use the packed "
                f"fl.server_opt optimizers with wire_quant"
            )
    if secure_agg:
        if wire_quant is None:
            raise ValueError(
                "secure_agg requires wire_quant — pairwise masks live "
                "in the shared-grid integer domain (fl.secagg); pass "
                "e.g. wire_quant='uint8'"
            )
        if mode == "ring":
            raise ValueError(
                "secure_agg runs the streaming/quorum coordinator "
                "topology — mode='ring' is a loud exclusion (stripe "
                "owners would each see a maskable subset)"
            )
        if sample is not None and sample != len(trainers):
            raise ValueError(
                "secure_agg and sample are mutually exclusive: the "
                "mask peer set is the round's full active roster"
            )
    if streaming_agg and not (compress_wire and packed_wire):
        raise ValueError(
            "streaming_agg requires compress_wire=True and "
            "packed_wire=True (the streamed unit is the packed wire "
            "buffer)"
        )
    if streaming_agg and aggregator is not None:
        raise ValueError(
            "streaming_agg and aggregator are mutually exclusive (a "
            "custom reducer needs the raw per-party values)"
        )
    if error_feedback and not (compress_wire and packed_wire):
        raise ValueError(
            "error_feedback requires compress_wire=True and "
            "packed_wire=True (the residual is carried on the packed "
            "wire buffer)"
        )
    if mode not in ("coordinator", "ring", "hierarchy"):
        raise ValueError(
            f"unknown mode {mode!r}: expected 'coordinator', 'ring' or "
            f"'hierarchy'"
        )
    if mode == "hierarchy":
        if wire_quant is None:
            raise ValueError(
                "mode='hierarchy' requires wire_quant: hierarchical "
                "aggregation is compressed-domain ONLY (float partial "
                "sums would re-associate a non-associative fold and "
                "silently break hierarchical == flat byte-identity) — "
                "pass e.g. wire_quant='uint8'"
            )
        if region_size is None or int(region_size) < 1:
            raise ValueError(
                "mode='hierarchy' requires region_size= (the "
                "deterministic partition width of the sorted roster), "
                f"got {region_size!r}"
            )
        if streaming_agg:
            raise ValueError(
                "mode='hierarchy' and streaming_agg are mutually "
                "exclusive: the hierarchy replaces the flat hub "
                "topology streaming_agg folds on (its fallback path "
                "streams on its own) — drop streaming_agg"
            )
        if sample is not None and sample != len(trainers):
            raise ValueError(
                "mode='hierarchy' requires full participation: "
                "sampling churns the region partition every round, "
                "re-striping every region ring — use "
                "mode='coordinator' for sampled rounds"
            )
        if secure_agg:
            raise ValueError(
                "mode='hierarchy' and secure_agg are mutually "
                "exclusive: pairwise masks only cancel over the FULL "
                "party set, so a region's partial sum would be "
                "un-finalizable ring noise — loud exclusion, never "
                "silent garbage"
            )
        if aggregator is not None:
            raise ValueError(
                "mode='hierarchy' and aggregator are mutually "
                "exclusive (a custom reducer needs the raw per-party "
                "values at one place)"
            )
    if region_size is not None and mode != "hierarchy":
        raise ValueError(
            "region_size only applies to mode='hierarchy' (it sets "
            "the deterministic region partition width)"
        )
    if region_branch is not None:
        if mode != "hierarchy":
            raise ValueError(
                "region_branch only applies to mode='hierarchy' (it "
                "sets the interior tree degree of the derived "
                "multi-level hierarchy)"
            )
        if int(region_branch) < 2:
            raise ValueError(
                f"region_branch must be >= 2 (a 1-ary interior level "
                f"folds nothing), got {region_branch!r}"
            )
    if region_quorum is not None:
        if mode != "hierarchy":
            raise ValueError(
                "region_quorum only applies to mode='hierarchy' (it "
                "sets the per-region minimum arrived count for the "
                "deadline-gated region cutoff)"
            )
        if int(region_quorum) < 1:
            raise ValueError(
                f"region_quorum must be >= 1 (the minimum arrived "
                f"member count per region), got {region_quorum!r}"
            )
    if region_deadline_s is not None:
        if region_quorum is None:
            raise ValueError(
                "region_deadline_s needs region_quorum= (the "
                "per-region minimum arrived count the deadline gates)"
            )
        if float(region_deadline_s) <= 0:
            raise ValueError(
                f"region_deadline_s must be positive, got "
                f"{region_deadline_s!r}"
            )
    if mode == "ring":
        if not (compress_wire and packed_wire):
            raise ValueError(
                "mode='ring' requires compress_wire=True and "
                "packed_wire=True (the striped unit is the packed wire "
                "buffer)"
            )
        if aggregator is not None:
            raise ValueError(
                "mode='ring' and aggregator are mutually exclusive (a "
                "custom reducer needs the raw per-party values at one "
                "place)"
            )
        if sample is not None and sample != len(trainers):
            raise ValueError(
                "mode='ring' requires full participation: sampling "
                "churns ring membership, re-striping the chunk grid "
                "and thrashing the per-peer delta caches every round — "
                "use mode='coordinator' for sampled rounds"
            )
        if streaming_agg:
            raise ValueError(
                "mode='ring' and streaming_agg are mutually exclusive: "
                "the ring replaces the hub topology streaming_agg "
                "folds on (the ring's fallback path streams on its "
                "own) — drop streaming_agg or use mode='coordinator'"
            )
    if coordinator is not None and coordinator not in trainers:
        raise ValueError(
            f"coordinator {coordinator!r} is not a training party "
            f"({sorted(trainers)})"
        )
    if ring_chunk_elems is not None and mode not in ("ring", "hierarchy"):
        raise ValueError(
            "ring_chunk_elems only applies to mode='ring' or "
            "mode='hierarchy' (it sets the stripe/chunk grid "
            "granularity)"
        )
    if quorum is not None:
        if not 1 <= int(quorum) <= len(trainers):
            raise ValueError(
                f"quorum must be in [1, {len(trainers)}], got {quorum}"
            )
        if not (compress_wire and packed_wire):
            raise ValueError(
                "quorum requires compress_wire=True and packed_wire=True "
                "(the quorum cutoff and the DGA late fold run on the "
                "packed wire buffer)"
            )
        incompat = {
            # Packed server optimizers compose with quorum (the
            # cutoff's subset refold reweights the step's effective
            # Σw, and the replicated state survives coordinator
            # failover) — only the legacy tree optimizers need the
            # fixed-roster classic loop.
            "server_opt": legacy_opt is not None,
            "aggregator": aggregator is not None,
            "sample": sample is not None and sample != len(trainers),
            "error_feedback": error_feedback,
            "overlap": overlap,
        }
        bad = [k for k, v in incompat.items() if v]
        if bad:
            raise ValueError(
                f"quorum is incompatible with {bad}: each needs the "
                "exact fixed-roster synchronous round boundary that "
                "k-of-n cutoffs and elastic membership give up (packed "
                "fl.server_opt optimizers DO compose with quorum)"
            )
    if round_deadline_s is not None:
        if quorum is None:
            raise ValueError(
                "round_deadline_s only applies with quorum= (it is the "
                "straggler cutoff of k-of-n rounds)"
            )
        if not round_deadline_s > 0:
            raise ValueError(
                f"round_deadline_s must be > 0, got {round_deadline_s}"
            )
    if join_ticket is not None and quorum is None:
        raise ValueError(
            "join_ticket only applies with quorum= (elastic membership "
            "rides the quorum round protocol)"
        )
    if round_log is not None and quorum is None:
        raise ValueError(
            "round_log only applies with quorum= (the classic loop has "
            "a fixed roster — there is nothing to log)"
        )
    if overlap:
        if not (compress_wire and packed_wire):
            raise ValueError(
                "overlap=True requires compress_wire=True and "
                "packed_wire=True (the overlapped aggregation unit is "
                "the packed wire buffer, and the DGA correction runs on "
                "it)"
            )
        if mode == "hierarchy":
            raise ValueError(
                "overlap=True is incompatible with mode='hierarchy' — "
                "the pipelined engine drives the coordinator/ring "
                "collectives from its comms lane; the hierarchy's "
                "region-cutoff/regroup protocol has no lane-callable "
                "collective yet (loud exclusion, never a silent flat "
                "fallback)"
            )
        if secure_agg:
            raise ValueError(
                "overlap=True is incompatible with secure_agg — "
                "pairwise masks are keyed by a synchronous (session, "
                "stream, round) tuple over the round's full roster; "
                "the pipelined lane's in-flight round would need a "
                "mask-recovery window that has never been exercised "
                "under overlap (loud exclusion)"
            )
        incompat = {
            # PACKED server optimizers compose via the unified
            # staleness recurrence (fl.overlap): the correction anchors
            # on the post-step broadcast, so the step consumes the mean
            # one-round-stale local displacement as its pseudo-gradient.
            # Only the legacy per-leaf tree optimizers still need the
            # materialized synchronous boundary.
            "server_opt": legacy_opt is not None,
            "aggregator": aggregator is not None,
            "sample": sample is not None and sample != len(trainers),
            "error_feedback": error_feedback,
            "checkpointer": checkpointer is not None,
        }
        bad = [k for k, v in incompat.items() if v]
        if bad:
            raise ValueError(
                f"overlap=True is incompatible with {bad}: each needs "
                "the exact synchronous round boundary (the overlapped "
                "aggregate lands one round late, under the next round's "
                "compute)"
            )


    if packed_opt is not None:
        if not (compress_wire and packed_wire):
            raise ValueError(
                "a packed server_opt (fl.server_opt) requires "
                "compress_wire=True and packed_wire=True — the fused "
                "step runs over the packed wire buffer"
            )
        incompat_s = {
            # The outgoing-wire EF residual corrects the model the
            # DRIVER pushes; under a server step the broadcast already
            # IS the stepped model — pair aggressive wire dtypes with
            # wire_quant (whose grid codec carries its own EF) instead.
            "error_feedback": error_feedback,
            # A custom reducer's output is not the weighted mean the
            # pseudo-gradient step assumes (and need not be packed).
            "aggregator": aggregator is not None,
            # The masked recovery window has not been exercised with a
            # post-finalize step — loud exclusion, never silently
            # unstepped or unmasked.
            "secure_agg": secure_agg,
            # A changing per-round subset is fine for the MEAN but the
            # legacy tree path is the one with sampling history; the
            # packed step has no sampled-round test yet.
            "sample": sample is not None and sample != len(trainers),
            # join_ticket COMPOSES since the object plane landed:
            # welcomes carry the server-opt spec + a content handle to
            # the replicated state, and the joiner resyncs through the
            # pull path (loud spec-mismatch guard in fl.quorum).
        }
        bad_s = [k for k, v in incompat_s.items() if v]
        if bad_s:
            raise ValueError(
                f"packed server_opt is incompatible with {bad_s} — "
                f"loud exclusion (see fl.server_opt's composition "
                f"notes)"
            )
    return {
        "wire_quant": _qname if wire_quant is not None else None,
        "checkpoint_every": checkpoint_every,
        "server_opt_kind": (
            "none" if server_opt is None
            else "packed" if packed_opt is not None
            else "fedopt"
        ),
    }


def run_fedavg_rounds(
    trainers: dict,
    params: Any,
    rounds: int,
    *,
    server_opt: Optional[ServerOptimizer] = None,
    weights: Optional[Sequence[float]] = None,
    compress_wire: bool = False,
    packed_wire: bool = False,
    checkpointer: Any = None,
    checkpoint_every: int = 0,
    on_round: Optional[Callable[[int, Any], None]] = None,
    sample: Optional[int] = None,
    sample_seed: int = 0,
    aggregator: Optional[Callable[[Sequence[Any]], Any]] = None,
    streaming_agg: bool = False,
    error_feedback: bool = False,
    wire_dtype: Any = None,
    wire_quant: Optional[Any] = None,
    mode: str = "coordinator",
    coordinator: Optional[str] = None,
    overlap: bool = False,
    timings: Optional[list] = None,
    ring_chunk_elems: Optional[int] = None,
    region_size: Optional[int] = None,
    region_branch: Optional[int] = None,
    region_quorum: Optional[int] = None,
    region_deadline_s: Optional[float] = None,
    quorum: Optional[int] = None,
    round_deadline_s: Optional[float] = None,
    join_ticket: Optional[dict] = None,
    round_log: Optional[list] = None,
    secure_agg: bool = False,
) -> Any:
    """Run ``rounds`` FedAvg rounds over party-pinned trainer actors.

    ``trainers``: ``{party: actor}`` where ``actor.train(params)``
    returns the party's updated tree (each party's actor runs only on
    its own silo).  Every controller passes the identical arguments.

    - ``server_opt``: apply a server optimizer to the round aggregate
      (plain replacement when ``None``).  A
      :class:`rayfed_tpu.fl.server_opt.PackedServerOpt` (``fl.fedac(λ,
      γ, β)`` / ``fl.server_momentum(lr, momentum)``) runs as ONE
      fused kernel over the packed wire buffers at the single
      finalize, cutting ROUNDS-to-target (FedAC), and composes with
      ``wire_quant``, ``streaming_agg``, ``quorum`` (the cutoff's
      subset refold reweights the step's effective Σw; the replicated
      state survives coordinator failover), ``mode="ring"`` (every
      controller steps the byte-identical assembly locally),
      ``mode="hierarchy"`` (the root steps once; the tree broadcast
      carries the post-step model) and ``overlap=True`` (the unified
      staleness recurrence: the DGA correction anchors on the
      post-step broadcast, so the step consumes the mean
      one-round-stale local displacement — see fl.overlap); requires
      ``compress_wire`` + ``packed_wire``; composes with
      ``join_ticket`` (welcomes carry the spec + a content handle to
      the replicated state, resolved through the object plane); loudly
      excluded with ``secure_agg``/``error_feedback``/``aggregator``/
      ``sample`` — see :mod:`rayfed_tpu.fl.server_opt` and
      ``docs/source/server_optimization.rst``.  A legacy
      :mod:`rayfed_tpu.fl.fedopt` ``ServerOptimizer`` keeps the
      per-leaf tree path (coordinator/ring topologies, no
      wire_quant/quorum).  Checkpoints stamp the server-opt config and
      carry its state; restoring across differing configs is refused
      loudly.
    - ``compress_wire``: halves the push bytes.  Trainer contract:
      ``train`` must call :func:`~rayfed_tpu.fl.decompress` on its
      argument (a no-op on full-precision input) and return
      ``compress(updated)`` — in pipelined rounds the averaged bf16
      tree flows straight back into ``train``; the driver decompresses
      only what it returns or feeds the server optimizer.
    - ``packed_wire``: with ``compress_wire``, use the packed single-
      buffer wire form (:class:`~rayfed_tpu.fl.PackedTree`): one fused
      cast kernel instead of per-leaf casts, one contiguous wire buffer
      instead of one per leaf.  ``decompress`` on the trainer side
      accepts either form transparently; trainers returning
      ``compress(updated, packed=True)`` keep the fast path end-to-end.
    - ``checkpointer``: a :class:`rayfed_tpu.checkpoint.FedCheckpointer`;
      resume happens automatically from its latest complete round.  If
      ``checkpoint_every`` is left at 0, it defaults to 1 (every round)
      — a checkpointer that resumes but never saves is a misconfig.
    - ``on_round(i, params)``: called after each materialized round.
    - ``sample``: partial participation — each round trains only a
      deterministic pseudo-random subset of ``sample`` parties (seeded
      by ``(sample_seed, round)``, so every controller draws the
      IDENTICAL subset and the seq-id streams stay aligned).
    - ``aggregator(values) -> tree``: replace the weighted mean with a
      custom reducer over the round's fetched contributions — e.g.
      :func:`rayfed_tpu.fl.tree_median`, ``functools.partial(
      fl.tree_trimmed_mean, trim=1)``, or a Krum selection.
      Materializes every round (the reducer needs raw values) and is
      mutually exclusive with ``weights``.
    - ``streaming_agg``: aggregate each round with
      :func:`rayfed_tpu.fl.streaming.streaming_aggregate` instead of
      the one-shot fetch+reduce: the coordinator folds each arriving
      contribution chunk into a donated on-device accumulator while
      later chunks are on the wire, and contributions/broadcasts ride
      per-peer **delta streams** (unchanged chunks never re-cross the
      wire).  Requires ``compress_wire`` + ``packed_wire`` (the
      streamed unit is the packed buffer) and materializes every round;
      bit-identical to the one-shot path.
    - ``error_feedback``: carry the wire quantization error of the
      outgoing (driver→trainer) compressed model into the next round
      (:class:`rayfed_tpu.fl.ErrorFeedback`) — keeps aggressive wire
      dtypes convergent.  Requires ``compress_wire`` + ``packed_wire``
      (the residual is carried on the packed buffer) and materializes
      every round (the driver must hold the round's tree to correct
      it).  Trainer-side updates compress inside the trainer's own
      ``train``; give each trainer its own ErrorFeedback instance for
      full bidirectional feedback.
    - ``wire_dtype``: the compressed wire dtype for the driver's
      outgoing pushes (default bf16).  Pair an aggressive choice (e.g.
      ``jnp.float8_e4m3fn``) with ``error_feedback=True``.
    - ``wire_quant``: aggregate **in the compressed domain** (``"uint8"``
      / ``"int8"``; see :mod:`rayfed_tpu.fl.quantize` and
      ``docs/source/compressed_aggregation.rst``).  Each round every
      controller derives the identical shared per-block grid from the
      previous round's observed aggregate delta, contributions are
      coded as ``update − shared model`` on that grid (with a carried
      error-feedback residual — the grid codec's OWN EF, which is why
      ``error_feedback=True`` is mutually exclusive) and the
      aggregators fold the integer codes with ONE fused rescale (+
      reference add) at finalize — roughly half the bf16 wire bytes
      AND half the fold's HBM traffic.  The first round has no
      observed delta and runs unquantized (bootstrap).  Requires
      ``compress_wire`` + ``packed_wire`` and ``streaming_agg=True``,
      ``mode="ring"`` or ``quorum=`` (quantized quorum rounds run the
      coordinator topology; ``quorum`` + ``mode="ring"`` +
      ``wire_quant`` is a loud exclusion); on the streaming and quorum
      paths the result broadcast is re-quantized too (fresh grid,
      carried in the payload), and quantized-quorum rounds are
      byte-identical to quantized-streaming rounds.  Integral
      non-negative ``weights`` only (example counts).
    - ``secure_agg``: **secure aggregation**
      (:mod:`rayfed_tpu.fl.secagg`; ``docs/source/
      secure_aggregation.rst``) — each party's quantized contribution
      is masked with pairwise masks derived from the transport's HELLO
      key agreement, so the coordinator (and any single eavesdropped
      payload) learns only the SUM of the round's updates, at zero
      extra wire bytes for the masks themselves (they are generated
      from agreed seeds, never transmitted; the masked codes widen to
      i32 on the wire).  The masked round's aggregate is BYTE-identical
      to the unmasked round's.  Requires ``wire_quant`` (masks live on
      the shared integer grid) with the streaming or quorum paths
      (``mode="ring"`` and ``sample`` are loud exclusions); composes
      with ``quorum`` — a mid-round dropout triggers pairwise mask
      recovery over the survivors, and coordinator failover re-runs
      recovery on the successor's stream.  The bootstrap round (no
      grid yet) runs unquantized AND unmasked.
    - ``mode``: the aggregation wire topology.  ``"coordinator"`` (the
      default) funnels contributions through one party (hub-and-spoke;
      with ``streaming_agg`` they fold as they arrive).  ``"ring"``
      replaces the hub with a chunk-striped **reduce-scatter +
      all-gather** over the sorted party ring
      (:func:`rayfed_tpu.fl.ring.ring_aggregate`): per-party traffic is
      ``~2·|model|`` independent of party count, and the result is
      byte-identical to the coordinator path.  Requires
      ``compress_wire`` + ``packed_wire`` (the striped unit is the
      packed buffer); full participation only (``sample`` churns ring
      membership, which would re-stripe the grid and thrash every delta
      cache — use the coordinator topology for sampled rounds); custom
      ``aggregator`` reducers need the raw values and stay
      coordinator-only.  When a ring round aborts mid-flight (peer
      death, poisoned hop), EVERY controller sees the abort (poison
      cascade + commit ring) and the driver re-aggregates the same
      round's updates over the coordinator topology — the round's
      training work is never lost.  ``"hierarchy"`` scales past what
      one flat structure can carry (:mod:`rayfed_tpu.fl.hierarchy`):
      the sorted roster partitions deterministically into regions of
      ``region_size``, each region runs the chunk-striped ring
      reduce-scatter internally, region coordinators stream integer
      partial sums up to a root, and ONE fused rescale finalizes —
      per-party traffic stays ~2·|model| and no node at any level
      sees O(N) ingress, with the aggregate BYTE-identical to the
      flat compressed-domain fold (integer adds are exact and
      associative).  Requires ``wire_quant`` (hierarchical float sums
      are a loud exclusion) and ``region_size``; the bootstrap round
      (no grid yet) runs the flat streaming path; a mid-round abort
      falls back to flat streaming (classic loop) or the quorum
      coordinator path (``quorum=``) for the SAME round, in lockstep.
    - ``region_size``: the deterministic partition width of
      ``mode="hierarchy"`` (regions are contiguous slices of the
      sorted roster — every controller derives the identical partition
      from the identical roster epoch, no negotiation).
    - ``region_branch``: interior tree degree of ``mode="hierarchy"``
      (>= 2).  When the region count exceeds the branch, the tree
      recurses: region coordinators group ``region_branch`` at a time
      under interior nodes, level by level, until one root remains —
      the regrouped integer folds stay byte-identical to the flat sum
      at any depth.  Default: one interior level (the 2-level tree).
    - ``region_quorum`` / ``region_deadline_s``: per-region quorum
      cutoffs for ``mode="hierarchy"``.  Once ``region_quorum``
      members of a region have delivered and ``region_deadline_s``
      has elapsed, the region coordinator folds the arrived subset
      and moves on — the root reweights to the true arrived Σw, so a
      straggling region delays only itself, not the tree, and the
      abort-and-flatten fallback is reserved for structural failures.
    - ``coordinator``: which party anchors coordinator-mode rounds and
      ring fallbacks (default: the canonically-first — ``min`` — party).
      Exposed mainly for tests and for deployments whose first party is
      bandwidth-poor; keep it STABLE across a training run, because
      every delta-stream cache is keyed by destination and a moving
      coordinator re-seeds full payloads on every peer it moves to.
      Under ``quorum=`` this names the INITIAL lease holder only:
      coordinator death or a coordinator ``fed.leave()`` rotates the
      lease to the deterministic successor (see
      :mod:`rayfed_tpu.fl.quorum`).

    - ``overlap``: double-buffer the rounds
      (:class:`rayfed_tpu.fl.overlap.PipelinedRoundRunner`): round *k*'s
      push + aggregation runs on a dedicated comms lane WHILE round
      *k+1* trains from each party's locally-updated model, and the
      late aggregate is folded in with the DGA correction
      ``w ← agg_k + (w_local − w_local_at_send)`` — per-round wall drops
      to ``max(compute, comms)`` at the cost of one round of bounded
      staleness (``overlap=False`` keeps today's exact synchronous
      semantics).  Requires ``compress_wire`` + ``packed_wire``;
      composes with ``mode="coordinator"`` (streaming aggregation),
      ``mode="ring"`` (with the same-round coordinator fallback on ring
      aborts), ``wire_quant`` and packed ``server_opt`` (the unified
      staleness recurrence — see :mod:`rayfed_tpu.fl.overlap`);
      mutually exclusive with legacy ``server_opt``, ``aggregator``,
      ``sample``, ``error_feedback``, checkpointing, ``secure_agg``,
      ``quorum`` and ``mode="hierarchy"`` (each needs the exact
      synchronous round boundary or a lane-callable collective).
    - ``timings``: optional list receiving one ``{"local_s", "push_s",
      "agg_s", "hidden_s"}`` dict per round (seconds; also logged at
      debug level).  ``hidden_s`` is the share of the round's comms wall
      that ran under local compute — 0 on the synchronous path by
      construction.  Requesting timings materializes every round (the
      lazy pipelined path has no per-round boundary to time).
    - ``ring_chunk_elems``: override the ring topology's stripe-grid
      granularity (``mode="ring"`` only; every controller must pass the
      same value — tests use it to stripe small models).

    - ``quorum``: **k-of-n rounds** — the round aggregates as soon as at
      least ``quorum`` contributions arrived once ``round_deadline_s``
      passes (or the stragglers provably cannot arrive), reweighted to
      the arrived Σw; a straggler's missed contribution folds into its
      NEXT round via the DGA correction instead of being dropped, and
      the live roster (``fed.join``/``fed.leave``/monitor-declared
      death) advances by coordinator announcement at round boundaries —
      see :mod:`rayfed_tpu.fl.quorum`.  The coordinator itself is a
      rotating crash-tolerant lease: on monitor-declared coordinator
      death every survivor fails over to the deterministic successor
      (next alive party on the sorted roster ring) and re-establishes
      the same round there, and a coordinator ``fed.leave()`` hands the
      lease over gracefully in its final announcement.  Requires
      ``compress_wire`` + ``packed_wire``; with ``quorum=len(trainers)``
      and no faults the result is byte-identical to the streaming path.
      Composes with ``mode="ring"`` (a ring abort re-aggregates the
      round over the coordinator topology with the quorum cutoff) and
      with ``checkpointer`` (snapshots carry round, roster epoch,
      member log, session and params; restore re-derives the
      coordinator from the restored roster).  Incompatible with
      ``server_opt``/``aggregator``/``sample``/``error_feedback``/
      ``overlap`` (each needs the exact fixed-roster synchronous
      boundary).
    - ``round_deadline_s``: the straggler cutoff for quorum rounds (and
      the per-wait deadline of quorum-mode ring rounds).  Without it a
      quorum round only cuts over when missing parties are DECLARED
      dead by the health monitor.
    - ``join_ticket``: the welcome dict returned by ``fed.join()`` — a
      (re)joining controller enters the in-progress quorum run at the
      welcome's round with the welcome's params; all other arguments
      must match the running controllers'.

    Without a server optimizer the rounds **pipeline**: the averaged
    model flows into the next round as a lazy ``FedObject`` (no
    ``fed.get`` barrier) and only the final round materializes.  A
    server optimizer (or ``on_round``/checkpointing) materializes every
    round — the server step is driver-side tree arithmetic.

    Returns the final global params (identical on every controller).
    """
    cfg = validate_round_config(
        trainers,
        rounds=rounds,
        server_opt=server_opt,
        weights=weights,
        compress_wire=compress_wire,
        packed_wire=packed_wire,
        checkpointer=checkpointer,
        checkpoint_every=checkpoint_every,
        sample=sample,
        aggregator=aggregator,
        streaming_agg=streaming_agg,
        error_feedback=error_feedback,
        wire_quant=wire_quant,
        mode=mode,
        coordinator=coordinator,
        overlap=overlap,
        ring_chunk_elems=ring_chunk_elems,
        region_size=region_size,
        region_branch=region_branch,
        region_quorum=region_quorum,
        region_deadline_s=region_deadline_s,
        quorum=quorum,
        round_deadline_s=round_deadline_s,
        join_ticket=join_ticket,
        round_log=round_log,
        secure_agg=secure_agg,
    )
    checkpoint_every = cfg["checkpoint_every"]
    _qname = cfg["wire_quant"]
    import numpy as _np

    # validate_round_config already classified server_opt — dispatch on
    # ITS verdict so the driver can never disagree with validation.
    packed_opt = (
        server_opt if cfg["server_opt_kind"] == "packed" else None
    )
    legacy_opt = (
        server_opt if cfg["server_opt_kind"] == "fedopt" else None
    )

    from rayfed_tpu.fed_object import FedObject
    from rayfed_tpu.fl.server_opt import (
        PackedServerOptimizer,
        check_snapshot_server_opt,
        describe_server_opt,
    )

    state = legacy_opt.init(params) if legacy_opt is not None else None
    sopt = PackedServerOptimizer(packed_opt) if packed_opt is not None else None
    # The checkpoint stamp for THIS run's server-opt config — every
    # snapshot carries it, and a restore across differing configs is
    # refused loudly (a silent momentum reset changes the trajectory
    # without failing anything).
    sopt_descr = describe_server_opt(server_opt)
    start_round = 0

    # Quorum rounds own their resume story (roster epoch + member log +
    # session ride the snapshot; see fl/quorum.py) — the classic
    # params/server-state restore below would strip all of that.
    if (
        checkpointer is not None
        and quorum is None
        and checkpointer.latest_round() is not None
    ):
        check_snapshot_server_opt(
            checkpointer.load_metadata().get("server_opt"), sopt_descr
        )
        target = {"params": params}
        if state is not None:
            target["server_state"] = state
        if sopt is not None:
            import jax.numpy as _sjnp

            from rayfed_tpu.fl.compression import pack_tree as _pt

            target["server_state"] = packed_opt.init(
                _pt(params, _sjnp.float32).buf
            )
        restored_round, snap = checkpointer.restore(target=target)
        params = snap["params"]
        if state is not None:
            state = snap["server_state"]
        if sopt is not None:
            sopt.load_state(snap["server_state"])
        start_round = restored_round
        if start_round >= rounds:
            return params

    # Pipelined mode only when nothing needs the materialized value
    # each round.
    pipeline = (
        server_opt is None
        and on_round is None
        and not checkpoint_every
        and aggregator is None  # a reducer needs the raw values
        and not streaming_agg  # streaming materializes at the reducer
        and not error_feedback  # the residual needs the driver's tree
        and mode == "coordinator"  # ring assembles (materializes) per round
        and timings is None  # per-round timing needs a round boundary
        and len(trainers) > 1
    )
    # Coordinator pinned to the canonically-first party unless the
    # caller overrides it — and then kept for the WHOLE run.  The churn
    # rationale: every delta-stream cache (contributions up, broadcast
    # down, ring fallback) is keyed by its destination party, so a
    # coordinator that rotates — e.g. "first active party" under client
    # sampling — would re-point every stream each round, re-seeding
    # full payloads everywhere and retaining stale multi-MB bases on
    # every former coordinator.  Stability beats load-spreading here;
    # spreading the load is what mode="ring" is for.
    coord = coordinator if coordinator is not None else min(trainers)
    # ``wire_dtype`` (default bf16) is where error feedback earns its
    # keep: fp8 wire halves bf16's bytes again, and the carried
    # residual is what keeps it convergent.
    import jax.numpy as _jnp

    wire_dt = _jnp.bfloat16 if wire_dtype is None else wire_dtype

    if quorum is not None:
        # k-of-n rounds with elastic membership own their loop shape
        # (roster-driven active set, DGA late folds, round-index-derived
        # rendezvous keys) — see fl/quorum.py.
        from rayfed_tpu.fl.quorum import run_quorum_rounds

        return run_quorum_rounds(
            trainers, params, rounds,
            quorum=int(quorum),
            round_deadline_s=round_deadline_s,
            weights=weights,
            coordinator=coord,
            wire_dtype=wire_dt,
            mode=mode,
            ring_chunk_elems=ring_chunk_elems,
            on_round=on_round,
            timings=timings,
            join_ticket=join_ticket,
            round_log=round_log,
            checkpointer=checkpointer,
            checkpoint_every=checkpoint_every,
            wire_quant=_qname if wire_quant is not None else None,
            secure_agg=secure_agg,
            region_size=region_size,
            region_branch=region_branch,
            region_quorum=region_quorum,
            region_deadline_s=region_deadline_s,
            server_opt=packed_opt,
        )

    if overlap:
        # The pipelined engine owns its own loop shape (double-buffered
        # rounds + DGA correction + comms lane) — see fl/overlap.py.
        # wire_quant and the packed server optimizer ride along: the
        # unified staleness recurrence makes the DGA correction commute
        # with delta-grid coding and with the accelerated server step.
        from rayfed_tpu.fl.overlap import PipelinedRoundRunner

        runner = PipelinedRoundRunner(
            trainers,
            weights=weights,
            mode=mode,
            coordinator=coord,
            wire_dtype=wire_dt,
            on_round=on_round,
            ring_chunk_elems=ring_chunk_elems,
            wire_quant=_qname,
            server_opt=sopt,
        )
        return runner.run(params, rounds, timings=timings)

    ef = ErrorFeedback(wire_dt) if error_feedback else None

    parties = list(trainers)

    def round_parties(r: int):
        if sample is None or sample == len(parties):
            return parties
        # Deterministic per-round subset: every controller draws the
        # identical parties (same seed, same round) or the seq-id
        # streams desync — see sample_parties for the canonical-order
        # contract.
        return sample_parties(parties, int(sample), sample_seed, r)

    current: Any = params  # tree, or FedObject in pipelined rounds
    # Compressed-domain state: the previous round's observed aggregate
    # delta (shared — derived from broadcast values only), the range
    # reference for the next round's grid.  None until one round has
    # been observed, so the first round always runs unquantized.  Held
    # as its per-block statistics (fl.quantize.BlockStats), all the grid
    # needs of it.
    quant_prev_delta = None
    # The grid chunking must BE the fold/stripe chunking: a ring round
    # with an overridden ring_chunk_elems quantizes on that same grid, or
    # ring_aggregate's chunk-match guard would abort (and silently fall
    # back) every quantized round.
    quant_chunk_elems = (
        ring_chunk_elems if mode in ("ring", "hierarchy") else None
    )

    sa_keys = None
    sa_session = None
    # Flight recorder (rayfed_tpu/telemetry.py): armed, every round is
    # a driver-side span carrying the SAME round/epoch keys the
    # transport stamps on frames, so the driver's view and the wire's
    # view join on one timeline.
    import time as _time

    from rayfed_tpu import telemetry as _telemetry
    from rayfed_tpu.runtime import get_runtime

    _rt = get_runtime()
    me = _rt.party
    if secure_agg:
        _transport = _rt.transport
        sa_keys = getattr(_transport, "secagg_keys", None)
        if sa_keys is None or not hasattr(
            _transport, "ensure_secagg_peer_keys"
        ):
            raise ValueError(
                "secure_agg needs the transport key-agreement plane "
                "(TransportManager.secagg_keys) — this transport has "
                "none"
            )
        # One HELLO ping per missing pair, before the first masked
        # round (fl.secagg / transport.secagg).
        _transport.ensure_secagg_peer_keys(parties)
        # Fresh mask-seed scope per run, drawn identically on every
        # controller: two runs in one process must never reuse a
        # (session, stream, round) seed — reused keystream over
        # different data is a two-time pad.
        sa_session = str(_rt.next_seq_id())

    for r in range(start_round, rounds):
        # A scope around the whole round.  The armed state is read here,
        # once a round: a recorder armed in the middle of the call is
        # seen from the next round.  On the lazy pipelined path the
        # driver thread only enqueues round r (``driver.dispatch``);
        # nothing is materialized for the span's sake.
        with _telemetry.span(
            "driver.dispatch" if pipeline else "driver.round",
            round=r, party=me, peer=coord,
        ) as round_span:
            active = round_parties(r)
            # Wire form: a driver-held tree is compressed before the push
            # (with the carried error-feedback residual folded in, when
            # enabled); a lazy FedObject from a pipelined round is already
            # the trainers' own (compressed) wire form.
            if compress_wire and not isinstance(current, FedObject):
                outgoing = (
                    ef.compress(current)
                    if ef is not None
                    else compress(
                        current, packed=packed_wire, wire_dtype=wire_dt
                    )
                )
            else:
                outgoing = current
            rec = None
            if timings is not None or (
                round_span is not None and not pipeline
            ):
                # Per-round breakdown (satellite of the overlap work): the
                # synchronous path exposes local/push/agg walls with
                # hidden_s pinned at 0 — comms fully serialize behind
                # compute here, which is exactly what overlap=True removes.
                rec = {
                    "local_s": 0.0, "push_s": 0.0, "agg_s": 0.0,
                    "hidden_s": 0.0,
                }
                t_r0 = _time.perf_counter()
            updates = [trainers[p].train.remote(outgoing) for p in active]
            if rec is not None and me in active:
                my_ref = updates[active.index(me)].get_local_ref()
                if my_ref is not None:
                    my_ref.add_done_callback(
                        lambda _ref, rec=rec, t0=t_r0: rec.__setitem__(
                            "local_s", _time.perf_counter() - t0
                        )
                    )
            if pipeline:
                last = r == rounds - 1
                current = aggregate(
                    updates,
                    weights,
                    mode="coordinator",
                    coordinator=coord,
                    materialize=last,
                )
                if last and compress_wire:
                    current = decompress(current)
                continue

            # aggregate() owns the wire topology for both the mean and a
            # custom reducer (coordinator-side reduce + broadcast at N>2) —
            # one place decides who talks to whom.  The streaming path rides
            # the same coordinator topology but folds contributions in as
            # their chunks arrive; the ring path replaces the hub with a
            # reduce-scatter + all-gather.  All three are bit-identical.
            #
            # With error feedback (or a server optimizer) the aggregate
            # must come back in f32: casting the mean to an aggressive
            # wire dtype here would re-quantize it with no residual to
            # compensate (the broadcast's delta cache still applies).
            agg_out_dtype = (
                "float32"
                if (error_feedback or server_opt is not None)
                else None
            )
            # Compressed-domain round: parties code their update as a DELTA
            # against the round's shared starting model (`current`, bit-
            # identical on every controller) on a grid derived from the
            # PREVIOUS round's observed aggregate delta — per-party deltas
            # live at that scale, so the 8-bit step resolves the signal,
            # not the ambient parameter range.  Every controller derives
            # the identical grid from the identical shared buffers (that IS
            # the negotiation; the fingerprint rides every quantized frame
            # and the aggregators verify it).  The FIRST round has no
            # observed delta yet and runs unquantized (bootstrap).
            round_grid = None
            round_ref = None
            if wire_quant is not None:
                from rayfed_tpu.fl import quantize as _qz
                from rayfed_tpu.fl.compression import pack_tree

                # The model as one float32 buffer, where it lives: the
                # codec, the aggregator and the delta read it there.
                with _telemetry.span("fl.quant.ref"):
                    round_ref = pack_tree(current, _jnp.float32).buf
                # That buffer is the model until the round's aggregate
                # replaces it: the trainers hold `outgoing`, nothing
                # below reads the tree.  Letting it go keeps ONE float32
                # model on the device, as when the reference was a host
                # array (two parties' steps share the chip's memory).
                current = None
                if quant_prev_delta is not None:
                    round_grid = _qz.make_round_grid(
                        quant_prev_delta, wire_dtype=_qname, mode="delta",
                        chunk_elems=quant_chunk_elems,
                        # Per-party deltas overshoot the aggregate delta
                        # (the mean averages them down) — give the grid
                        # headroom; what still clips rides the EF residual.
                        expand=_QUANT_DELTA_EXPAND,
                    )
            # Packed server optimization (fl.server_opt): the round's
            # shared starting buffer anchors the step (applied at the
            # finalizing node for streaming/quorum/hierarchy, locally on
            # every controller for ring/classic — deterministic f32 on
            # byte-identical input either way) and the post-round state
            # resync every controller runs from the broadcast pair.
            step_fn = None
            x_srv = None
            if sopt is not None:
                if round_ref is not None:
                    x_srv = round_ref
                else:
                    from rayfed_tpu.fl.compression import pack_tree as _pt2

                    x_srv = _np.asarray(_pt2(current, _jnp.float32).buf)
                sopt.ensure(x_srv)
                step_fn = sopt.step_fn(x_srv)
            # Secure aggregation: this party's round masker (pairwise
            # seeds toward every active peer at its own fold weight); the
            # keystream expansion prefetches on a background thread so it
            # overlaps training/the wire instead of the round's critical
            # path.  The bootstrap round (no grid) runs unmasked.
            round_masker = None
            if secure_agg and round_grid is not None and me in trainers:
                from rayfed_tpu.fl import secagg as _sa
                from rayfed_tpu.fl.fedavg import quant_weights

                _iw, _ = quant_weights(
                    None if weights is None
                    else [float(w) for w in weights],
                    len(active),
                )
                round_masker = _sa.RoundMasker(
                    sa_keys, me, [p for p in active if p != me],
                    session=sa_session, stream="fedavg", round_index=r,
                    weight=_iw[active.index(me)],
                )
                round_masker.prefetch(round_grid.total_elems)
            if mode == "hierarchy":
                from rayfed_tpu.fl.streaming import streaming_aggregate

                if round_grid is None:
                    # Bootstrap round: no shared grid has been observed yet
                    # and hierarchy is compressed-domain only — run the
                    # flat streaming round (exactly the quantized loop's
                    # own bootstrap), hierarchical from the next round.
                    avg = streaming_aggregate(
                        updates, weights, stream="fedavg",
                        coordinator=coord, out_dtype=agg_out_dtype,
                        timings=rec,
                        server_step=step_fn,
                    )
                else:
                    from rayfed_tpu.fl.hierarchy import (
                        HIER_STATS,
                        HierarchyRoundError,
                        hierarchy_aggregate,
                    )

                    try:
                        avg = hierarchy_aggregate(
                            updates, weights,
                            region_size=int(region_size),
                            region_branch=region_branch,
                            region_quorum=region_quorum,
                            region_deadline_s=region_deadline_s,
                            stream="fedavg",
                            server_step=step_fn,
                            quant=round_grid, quant_ref=round_ref,
                            quant_scope="fedavg",
                            # Quantize the broadcast down the tree too —
                            # the downlink is the other half of the
                            # round's bytes (shared quantize_downlink
                            # producer).
                            quant_downlink=True,
                            round_tag=r, timings=rec,
                        )
                    except HierarchyRoundError as e:
                        # The abort reached every controller (tree-shaped
                        # poison cascade + commit/release), so all of them
                        # take this branch in lockstep: re-aggregate the
                        # SAME round's updates over the flat streaming
                        # path — owners still hold them, and the shared
                        # RoundCodec re-quantizes with the SAME residual.
                        logger.warning(
                            "hierarchy round %d aborted (%s); falling back "
                            "to flat streaming aggregation at %r", r, e,
                            coord,
                        )
                        HIER_STATS["fallback_rounds"] += 1
                        avg = streaming_aggregate(
                            updates, weights, stream="fedavg",
                            coordinator=coord, timings=rec,
                            quant=round_grid, quant_ref=round_ref,
                            quant_scope="fedavg",
                            # The SAME step from the SAME state: the abort
                            # happened before any resync, so the flat
                            # re-run's step is bit-identical to the one the
                            # hierarchy root would have applied.
                            server_step=step_fn,
                        )
            elif mode == "ring":
                from rayfed_tpu.fl.ring import (
                    RING_STATS,
                    RingRoundError,
                    ring_aggregate,
                )

                try:
                    avg = ring_aggregate(
                        updates, weights, stream="fedavg",
                        out_dtype=agg_out_dtype,
                        chunk_elems=ring_chunk_elems, timings=rec,
                        quant=round_grid, quant_ref=round_ref,
                        quant_scope="fedavg",
                    )
                    if step_fn is not None:
                        # The ring has no downlink — every controller holds
                        # the byte-identical assembled aggregate, so each
                        # applies the same deterministic f32 step locally
                        # and all byte-agree on the post-step model.
                        avg = step_fn(avg)
                except RingRoundError as e:
                    # The abort reached every controller (poison cascade +
                    # commit ring), so all of them take this branch in
                    # lockstep: re-aggregate the SAME round's updates over
                    # the coordinator topology — owners still hold them, so
                    # no training work is lost.
                    from rayfed_tpu.fl.streaming import streaming_aggregate

                    logger.warning(
                        "ring round %d aborted (%s); falling back to "
                        "coordinator aggregation at %r", r, e, coord,
                    )
                    RING_STATS["fallback_rounds"] += 1
                    avg = streaming_aggregate(
                        updates, weights, stream="fedavg",
                        coordinator=coord, out_dtype=agg_out_dtype,
                        timings=rec,
                        # Same grid, same (uncommitted) residual: the
                        # fallback re-quantizes the identical codes the
                        # ring round would have folded.  Downlink stays
                        # plain — this is the recovery path, keep it
                        # simple.  The server step re-runs from the same
                        # (never-resynced) state at the coordinator.
                        quant=round_grid, quant_ref=round_ref,
                        quant_scope="fedavg",
                        server_step=step_fn,
                    )
            elif streaming_agg:
                from rayfed_tpu.fl.streaming import streaming_aggregate

                avg = streaming_aggregate(
                    updates, weights, stream="fedavg",
                    coordinator=coord,
                    out_dtype=agg_out_dtype,
                    timings=rec,
                    quant=round_grid, quant_ref=round_ref,
                    quant_scope="fedavg",
                    # Quantize the result broadcast too: the downlink is
                    # the other half of the round's bytes.  Under a server
                    # step the coordinator steps FIRST, so the downlink
                    # recode's fresh grid is ranged by the post-step delta.
                    quant_downlink=round_grid is not None,
                    secagg=round_masker,
                    server_step=step_fn,
                )
            else:
                t_a0 = _time.perf_counter() if rec is not None else 0.0
                avg = aggregate(
                    updates, weights, reducer=aggregator, coordinator=coord
                )
                if step_fn is not None:
                    # Every controller holds the byte-identical broadcast
                    # mean; the deterministic f32 step keeps them agreeing.
                    avg = step_fn(avg)
                if rec is not None:
                    rec["agg_s"] = _time.perf_counter() - t_a0
            if sopt is not None:
                # Every controller advances its state replica from the
                # round's byte-agreed broadcast pair (the broadcast IS the
                # post-step model) — all replicas stay byte-identical with
                # zero extra wire bytes (fl.server_opt).
                sopt.resync(x_srv, _np.asarray(avg.buf))
            if wire_quant is not None:
                # What the grid must cover next round: how far the global
                # model just moved, per block.  Derived from broadcast
                # values only, so it is bit-identical on every controller
                # (under server_opt: the POST-step delta — the grid ranges
                # over the model movement the step actually realized).
                # Its block statistics are taken now, on the device and
                # while the chip is idle; make_round_grid fetches them
                # next round.  Nothing crosses to the host here.
                with _telemetry.span("fl.quant.delta"):
                    quant_prev_delta = _qz.block_stats(
                        avg.buf, round_ref, chunk_elems=quant_chunk_elems
                    )
                # Let go before the unpack below makes the next model's
                # leaves: two model-sized buffers at a time, not three.
                round_ref = None
            if compress_wire:
                avg = decompress(avg)
            if legacy_opt is not None:
                current, state = legacy_opt.apply(current, avg, state)
            else:
                current = avg
            if on_round is not None:
                on_round(r, current)
            if checkpoint_every and (r + 1) % checkpoint_every == 0:
                snap = {"params": current}
                if state is not None:
                    snap["server_state"] = state
                if sopt is not None:
                    snap["server_state"] = sopt.state
                checkpointer.save(
                    r + 1, snap, metadata={"server_opt": sopt_descr}
                )
            if rec is not None:
                # The aggregation call blocks on this party's own training
                # output before any byte can move, so its measured walls
                # include the local wait — subtract it to report the comms-
                # only window (what overlap=True would hide).
                rec["push_s"] = max(0.0, rec["push_s"] - rec["local_s"])
                rec["agg_s"] = max(0.0, rec["agg_s"] - rec["local_s"])
                # Correlation stamp: the SAME keys the transport rides on
                # every frame (wire.ROUND_TAG_KEY / EPOCH_TAG_KEY), so a
                # timings row joins the wire's view of its round on one
                # timeline.  Classic fedavg has no roster epoch — None.
                rec["round"] = r
                rec["epoch"] = None
                rec["coordinator"] = coord
                if timings is not None:
                    timings.append(rec)
                if round_span is not None:
                    round_span.detail = {
                        k: (round(v, 6) if isinstance(v, float) else v)
                        for k, v in rec.items()
                    }
                logger.debug(
                    "round %d timings: local=%.3fs push=%.3fs agg=%.3fs "
                    "hidden=%.3fs", r, rec["local_s"], rec["push_s"],
                    rec["agg_s"], rec["hidden_s"],
                )

    return current
