"""Chunk-striped ring aggregation: reduce-scatter + all-gather FedAvg.

The coordinator topology (``fl.aggregate`` / ``fl.streaming``) funnels
every contribution into one party: the coordinator moves
``2·(N-1)·|model|`` bytes per round while every other party moves
``~|model|`` — bench r05 put ~620 ms of coordinator-serialized wire time
on a 4-party ResNet round.  Here the round is a **reduce-scatter
followed by an all-gather** over the sorted party ring (the weight-
update sharding of arXiv:2004.13336 applied to the cross-silo wire):

1. **Stripe layout.**  The packed buffer's canonical chunk grid
   (:func:`rayfed_tpu.fl.fedavg.packed_block_grid` — the transport's
   4 MB chunks) is striped round-robin across the N ring parties
   (:func:`~rayfed_tpu.fl.fedavg.packed_stripe_schedule`): block ``b``
   belongs to stripe ``b % N``, stripe ``k`` is owned by the ring's
   ``k``-th party.  The schedule is derived independently by every
   party from the same constants — it is part of the cross-party
   contract, like the wire format.

2. **Reduce-scatter.**  Every party slices its own packed contribution
   into per-stripe compacted payloads and pushes each to that stripe's
   owner on a stable delta stream (``{stream}/rs``) — round-over-round
   unchanged chunks never cross the wire (wire v3 per-chunk CRCs +
   delta bitmap).  Each owner folds the arriving stripe blocks into a
   donated f32 accumulator with the **party-order-per-block schedule**
   (:class:`rayfed_tpu.fl.streaming.StripeAggregator`), carrying the
   (Σ weight·payload, Σ weight) pair, and finalizes with the shared
   one-fused-divide (:func:`~rayfed_tpu.fl.fedavg
   .finalize_packed_stripe`).  Both the fold chain and the finalize are
   elementwise, so each reduced stripe is byte-identical to the same
   element range of ``packed_weighted_sum`` — and therefore of the
   coordinator path — regardless of arrival order.

3. **All-gather.**  Each owner sends its reduced stripe to its ring
   successor; every party forwards what it receives onward until the
   stripe has visited the whole ring (N-1 hops), also on per-stripe
   delta streams.  Every party assembles the N stripes back onto the
   chunk grid into the identical result ``PackedTree``.

Per-party traffic is ``~2·|model|`` **independent of N** (vs the hub's
``2·(N-1)·|model|`` at the coordinator): the difference between 4
parties and 40.

**Commit ring.**  A failure anywhere must make *every* party abandon
the round together (a half-fallen-back cluster desyncs its seq-id
streams).  Failures propagate two ways: the failing party poisons every
rendezvous key it was going to produce (reusing the transport's poison
+ frame-abort machinery), and a 2-pass token ring (commit → release)
runs after assembly so a party that already has all its bytes still
learns that someone else didn't.  As with any atomic commit, a crash
inside the tiny release pass itself can still strand successors — that
residual window is bounded by two token hops and backstopped by the
recv deadline; the bulk phases (the multi-MB transfers, where failures
actually happen) are fully covered.

``run_fedavg_rounds(mode="ring")`` drives this per round and falls back
to the coordinator topology (``streaming_aggregate``) for the round
when the ring aborts — same bytes-identical result, no lost round.
"""

from __future__ import annotations

import json
import logging
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

logger = logging.getLogger(__name__)

# Version of the ring stripe manifest ("rsm" sideband leaf) — bump when
# make_stripe_meta's schema OR SEMANTICS change.  Fingerprinted
# (together with the schema) by tool/check_wire_format.py: stripe
# payloads are a cross-party contract layered on the ordinary payload
# manifest, so drift must be deliberate.  The frame layout itself is
# untouched.
# History: 1 = original; 2 = optional "qg" field (the shared
# quantization grid's fingerprint on compressed-domain "rs" stripes —
# receivers cross-check it before folding integer codes); 3 = "ag"
# stripes of a compressed-domain round carry grid CODES (dt = the
# grid's integer dtype, "qg" present) instead of f32 — the gather hop
# is coded on the shared round grid (see ring_aggregate's quant docs).
RING_STRIPE_VERSION = 3

# Module-level round counters (mirrors rayfed_tpu.metrics' style of
# cheap global accounting): the trainer's fallback path and tests read
# these to assert a ring round actually completed / aborted.
RING_STATS: Dict[str, int] = {
    "rounds_completed": 0,
    "rounds_aborted": 0,
    "fallback_rounds": 0,
}

# Test-only fault injection: when set, called with the phase name
# ("local", "rs", "reduce", "ag", "commit") at each step of the member
# flow.  Raising from the hook simulates a mid-round failure at exactly
# that phase (the mid-round peer-death tests drive the fallback path
# through this).
_fault_hook: Optional[Callable[[str], None]] = None


def _maybe_fault(phase: str) -> None:
    if _fault_hook is not None:
        _fault_hook(phase)


# Seq ids one ring_aggregate call consumes — callers pre-allocating ids
# for an off-main-thread call (fl.overlap's comms lane) draw exactly
# this many from runtime.next_seq_id() in program order.
RING_SEQ_IDS = 5


class RingRoundError(RuntimeError):
    """A ring round aborted (peer death, wire failure, poisoned hop).

    The round's contributions are still intact on their owners —
    re-aggregate them over the coordinator topology
    (``run_fedavg_rounds(mode="ring")`` does exactly that).
    """


def make_stripe_meta(
    stripe: int,
    n_stripes: int,
    nblocks: int,
    total_elems: int,
    dtype: str,
    phase: str,
    qgrid_fp: Optional[int] = None,
) -> Dict[str, Any]:
    """The ``rsm`` sideband of a stripe payload — single producer of its
    schema (``tool/check_wire_format.py`` fingerprints it).

    ``phase`` is ``"rs"`` (a raw stripe contribution) or ``"ag"`` (a
    reduced stripe on the gather ring).  Receivers cross-check it
    against their independently derived schedule so a mis-wired payload
    fails loudly instead of folding into the wrong offsets.

    ``qgrid_fp`` (v2, compressed-domain rounds): the shared
    quantization grid's fingerprint — "rs" stripes carry integer codes
    whose meaning IS the grid, so a receiver folding them into its i32
    accumulator first proves both ends derived the identical grid.
    """
    rsm = {
        "v": RING_STRIPE_VERSION,
        "s": int(stripe),
        "n": int(n_stripes),
        "nb": int(nblocks),
        "el": int(total_elems),
        "dt": str(dtype),
        "ph": str(phase),
    }
    if qgrid_fp is not None:
        rsm["qg"] = int(qgrid_fp)
    return rsm


def _stripe_slice(buf: np.ndarray, blocks: Sequence[int], chunk_elems: int,
                  total_elems: int) -> np.ndarray:
    """Compact the stripe's blocks out of the packed buffer, in
    ascending block order (the order the fold schedule assumes)."""
    parts = [
        buf[b * chunk_elems : min((b + 1) * chunk_elems, total_elems)]
        for b in blocks
    ]
    if not parts:
        return np.empty(0, buf.dtype)
    if len(parts) == 1:
        return np.ascontiguousarray(parts[0])
    return np.concatenate(parts)


def _stripe_elems(blocks: Sequence[int], chunk_elems: int, nblocks: int,
                  total_elems: int) -> int:
    n = len(blocks) * chunk_elems
    if blocks and blocks[-1] == nblocks - 1:
        n -= nblocks * chunk_elems - total_elems  # short tail block
    return n


def code_gather_stripe(
    stripe, ref_slice, scales, zps, chunk_elems: int, wire_dtype: str
) -> np.ndarray:
    """Code a finalized f32 stripe onto the SHARED round grid's rows —
    the quantized ring's gather-hop coding (ROADMAP 2a: the
    reduce-scatter was already integer; this closes the f32 gather).

    Mirrors the coordinator topology's ``quantize_downlink``: the
    finalized stripe is the round's OUTPUT, so its coding error is the
    same downlink-class error every quantized broadcast already
    carries — and because the round grid is shared and the coding is
    block-local (the same fused kernels ``fl.quantize`` compiles for
    the full buffer, applied to the stripe's rows), every controller
    decodes the identical bytes, and the assembled ring result equals
    the full-buffer recode of the exact aggregate
    (``quantize_packed(exact, grid, ref).dequantize(...)``) bit for
    bit.  The stripe OWNER substitutes the decoded codes for its own
    stripe too, so ring parties byte-agree by construction.
    """
    import jax.numpy as jnp

    from rayfed_tpu.fl.quantize import _quantize_kernel

    arr = np.asarray(stripe, np.float32).reshape(-1)
    se = int(arr.size)
    with_ref = ref_slice is not None
    ref = (
        np.asarray(ref_slice, np.float32).reshape(-1)
        if with_ref else jnp.zeros(0, jnp.float32)
    )
    qbuf, _ = _quantize_kernel(
        int(chunk_elems), se, str(wire_dtype), with_ref
    )(arr, ref, np.asarray(scales, np.float32),
      np.asarray(zps, np.float32), jnp.zeros(se, jnp.float32))
    return np.asarray(qbuf)


def decode_gather_stripe(
    codes, ref_slice, scales, zps, chunk_elems: int, out_dtype
) -> np.ndarray:
    """Decode a gather-hop stripe's grid codes back to the output dtype
    — the receiver half of :func:`code_gather_stripe` (identical on
    every controller: shared grid rows + shared reference slice)."""
    import jax.numpy as jnp

    from rayfed_tpu.fl.quantize import _dequantize_kernel

    arr = np.asarray(codes).reshape(-1)
    se = int(arr.size)
    with_ref = ref_slice is not None
    ref = (
        np.asarray(ref_slice, np.float32).reshape(-1)
        if with_ref else jnp.zeros(0, jnp.float32)
    )
    out = _dequantize_kernel(
        int(chunk_elems), se, str(arr.dtype), np.dtype(out_dtype).name,
        with_ref,
    )(arr, ref, np.asarray(scales, np.float32),
      np.asarray(zps, np.float32))
    return np.asarray(out)


def _check_meta(meta_json: str, want: Dict[str, Any]) -> None:
    # "rsm", not "meta": this is the ring stripe manifest (a payload-
    # level contract fingerprinted via ring_stripe_schema), NOT frame
    # metadata — fedlint FED006 polices literal keys on the latter.
    rsm = json.loads(meta_json)
    if rsm.get("v", 0) > RING_STRIPE_VERSION:
        raise ValueError(
            f"stripe payload uses ring manifest v{rsm.get('v')}; this "
            f"party understands up to v{RING_STRIPE_VERSION}"
        )
    for key, expect in want.items():
        if rsm.get(key) != expect:
            raise ValueError(
                f"stripe manifest mismatch: {key}={rsm.get(key)!r}, "
                f"expected {expect!r} — ring peers disagree on the "
                f"stripe schedule"
            )


def ring_aggregate(
    fed_objects: Sequence[Any],
    weights: Optional[Sequence[float]] = None,
    *,
    stream: str = "ring",
    timeout: Optional[float] = None,
    out_dtype: Any = None,
    chunk_elems: Optional[int] = None,
    seq_ids: Optional[Sequence[int]] = None,
    round_tag: Optional[int] = None,
    timings: Optional[Dict[str, float]] = None,
    expect_parties: Optional[Sequence[str]] = None,
    quant: Optional[Any] = None,
    quant_ref: Optional[Any] = None,
    quant_scope: Optional[str] = None,
) -> Any:
    """FedAvg round over the chunk-striped ring (see module docstring).

    Drop-in for ``streaming_aggregate`` when the contributions are
    PackedTrees with one contribution per party: every controller calls
    it at the same program point with the same arguments.  Returns the
    averaged PackedTree on every party — byte-identical to
    ``packed_weighted_sum(values, weights)`` over the same contribution
    order, and therefore to the coordinator topology.

    ``stream`` scopes the delta caches (keep it constant across
    rounds); ``out_dtype`` as in ``streaming_aggregate`` (keep f32 for
    server optimizers / error feedback).  ``chunk_elems`` overrides the
    canonical grid granularity — every controller must pass the same
    value (tests use it to stripe small payloads).  Aborted rounds
    raise :class:`RingRoundError` on **every** controller (poison
    cascade + commit ring) so callers can fall back in lockstep.

    ``seq_ids``: :data:`RING_SEQ_IDS` pre-allocated rendezvous ids (in
    ``next_seq_id`` order).  Default (None) allocates them here; a call
    dispatched to a background lane (:mod:`rayfed_tpu.fl.overlap`) MUST
    pass main-thread-drawn ids — see
    :func:`~rayfed_tpu.fl.streaming.streaming_aggregate`.  ``round_tag``
    stamps every frame of the round with the round index
    (``wire.ROUND_TAG_KEY``).  ``timings`` (optional dict) receives
    ``push_s`` (reduce-scatter pushes ACKed) and ``agg_s`` (whole-call
    wall).

    ``quant``: the round's shared
    :class:`~rayfed_tpu.fl.quantize.QuantGrid` — the reduce-scatter
    runs **in the compressed domain**: each party quantizes its
    contribution onto the grid (pre-quantized contributions pass a
    fingerprint check), stripe payloads carry integer codes (half the
    bf16 bytes) with the grid fingerprint in their ``rsm`` manifest,
    and each stripe owner folds codes into a donated i32 accumulator
    with ONE fused rescale at finalize
    (:class:`~rayfed_tpu.fl.streaming.StripeAggregator` integer path).
    The all-gather hop is coded on the SAME shared round grid
    (:func:`code_gather_stripe` — each owner ships its finalized
    stripe as grid codes, relays forward the codes, and every party
    *owner included* assembles the decoded codes), so BOTH halves of
    the ring round ride integer bytes.  The gather coding is the
    ring's analogue of the coordinator path's quantized downlink: the
    finalized stripes are the round's OUTPUT, so the (tiny,
    grid-step-bounded) coding error is the same downlink-class error
    every quantized broadcast already carries — and because the grid
    is shared and coding is block-local, the assembled result is
    byte-identical on every controller and equals the full-buffer
    recode of the exact aggregate:
    ``quantize_packed(packed_quantized_sum(...), grid,
    ref).dequantize(...)``.  ``quant_ref``: the round's
    shared reference buffer for ``mode="delta"`` grids (parties code
    ``update − ref``; each stripe owner's finalize adds back its
    compacted reference slice).  ``out_dtype`` defaults to f32.
    ``quant_scope`` keys the per-process error-feedback
    residual exactly as in ``streaming_aggregate`` — committed only
    when the round lands, so the coordinator fallback re-quantizes the
    SAME update with the SAME residual after a ring abort.

    ``expect_parties``: the controllers expected to be LIVE this round
    (default: the whole cluster config).  Elastic-membership callers
    (``fl.quorum``) pass the current roster so a departed/dead party is
    not treated as a non-member controller owed the result broadcast —
    a checked send to a corpse would otherwise abort every ring round
    after churn.  Must be identical on every controller (it is: the
    roster is announcement-driven).
    """
    from rayfed_tpu.fed_object import FedObject
    from rayfed_tpu.fl.fedavg import (
        _check_weights,
        packed_block_grid,
        packed_stripe_schedule,
    )
    from rayfed_tpu.fl.streaming import DEFAULT_CHUNK_ELEMS
    from rayfed_tpu.runtime import get_runtime

    runtime = get_runtime()
    objs = list(fed_objects)
    if not objs:
        raise ValueError("ring_aggregate needs at least one contribution")
    for obj in objs:
        if not isinstance(obj, FedObject):
            raise TypeError(
                "ring_aggregate consumes FedObjects (party-owned "
                f"contributions), got {type(obj).__name__}"
            )
    owners = [obj.get_party() for obj in objs]
    if len(set(owners)) != len(owners):
        raise ValueError(
            "ring_aggregate needs exactly one contribution per party "
            f"(owners: {owners}) — aggregate duplicates locally first"
        )
    if weights is not None:
        if len(weights) != len(objs):
            raise ValueError(
                f"{len(weights)} weights for {len(objs)} contributions"
            )
        weights = [float(w) for w in weights]
        total_w = _check_weights(weights)
    else:
        total_w = float(len(objs))

    # The ring: contribution owners in sorted order.  Stripe k is owned
    # by ring[k]; the FOLD order stays the fed_objects order (the same
    # order the coordinator path reduces in), which need not equal ring
    # order — idx_of maps between the two.
    ring = sorted(owners)
    n = len(ring)
    idx_of = {obj.get_party(): i for i, obj in enumerate(objs)}

    # Seq ids — allocated unconditionally and identically on every
    # controller (success, abort and non-member paths all consume the
    # same five), preserving the rendezvous determinism contract.
    if seq_ids is None:
        rs_id = runtime.next_seq_id()
        ag_id = runtime.next_seq_id()
        commit_id = runtime.next_seq_id()
        release_id = runtime.next_seq_id()
        nm_id = runtime.next_seq_id()
    else:
        rs_id, ag_id, commit_id, release_id, nm_id = seq_ids
    import time as _time

    from rayfed_tpu import telemetry as _telemetry

    t_call0 = _time.perf_counter()
    t_mark = t_call0
    me = runtime.party
    # Flight-recorder ring phase boundaries (reduce_scatter /
    # all_gather / commit).  Disarmed: a bare perf_counter read per
    # phase; armed: a ring append — never I/O.
    _phase_span = _telemetry.phase_spanner(
        "ring", round=round_tag, party=me,
    )

    backstop = (
        timeout if timeout is not None
        else runtime.job_config.recv_backstop_s
    )
    parties = (
        list(expect_parties) if expect_parties is not None
        else list(runtime.cluster_config.parties)
    )
    non_members = [p for p in parties if p not in set(ring)]

    from rayfed_tpu.proxy import (
        recv_on_runtime,
        send_many_on_runtime,
        send_on_runtime,
    )

    if me not in idx_of:
        # Non-member controller (its party contributes nothing this
        # round): the first ring party broadcasts the assembled result
        # before its commit, and a release token after the commit ring
        # ran — consuming BOTH keeps this controller's success/abort
        # decision in lockstep with the members'.
        try:
            ref = recv_on_runtime(runtime, ring[0], nm_id, nm_id)
            result = ref.resolve(timeout=backstop)
            recv_on_runtime(
                runtime, ring[0], f"{release_id}.nm", release_id
            ).resolve(timeout=backstop)
            RING_STATS["rounds_completed"] += 1
            return result
        except (KeyboardInterrupt, SystemExit):
            raise
        except BaseException as exc:
            RING_STATS["rounds_aborted"] += 1
            raise RingRoundError(
                f"ring round broadcast from {ring[0]!r} failed: {exc!r}"
            ) from exc

    from rayfed_tpu.transport.manager import ring_neighbors

    transport = runtime.transport
    m = ring.index(me)
    my_idx = idx_of[me]
    pred, succ = ring_neighbors(ring, me)
    chunk_elems = (
        int(chunk_elems) if chunk_elems else DEFAULT_CHUNK_ELEMS
    )

    def _broadcast_non_members(result) -> None:
        """Result to non-member controllers — checked, so a failed
        broadcast aborts the round instead of leaving them parked."""
        refs = send_many_on_runtime(
            runtime, non_members, result, nm_id, nm_id,
            stream=f"{stream}/nm", round_tag=round_tag,
        )
        for p, ref in refs.items():
            if not ref.resolve(timeout=backstop):
                raise RingRoundError(
                    f"result broadcast to non-member {p!r} failed"
                )

    def _release_non_members() -> None:
        """Post-commit release tokens (tiny).  Failures here are the
        same residual commit-window class as a member dying inside the
        release pass: the non-member aborts at its backstop — log, but
        the members' round already committed."""
        refs = send_many_on_runtime(
            runtime, non_members, {"ok": 1}, f"{release_id}.nm",
            release_id, round_tag=round_tag,
        )
        for p, ref in refs.items():
            if not ref.resolve(timeout=backstop):  # pragma: no cover
                logger.warning(
                    "[%s] non-member release token to %s failed",
                    me, p,
                )

    # Compressed-domain plumbing: ONE shared sender-side codec
    # discipline (fl.quantize.RoundCodec — grid-fingerprint check + EF
    # two-phase commit, identical across streaming/ring/quorum, so the
    # ring-abort → coordinator-fallback path re-quantizes with the
    # SAME residual by construction).  No-op when quant is None.
    from rayfed_tpu.fl.quantize import RoundCodec

    codec = RoundCodec(quant, quant_ref, quant_scope)
    # The codec codes against the reference where it lives; the stripes
    # are host buffers, so their reference slices are cut from host
    # bytes, fetched here.
    qref = None if codec.ref is None else np.asarray(codec.ref)
    q_descriptor = codec.descriptor
    _to_wire = codec.to_wire
    _quant_commit = codec.commit
    _quant_rollback = codec.rollback

    if n == 1:
        # Degenerate single-party ring: reduce locally with the same
        # fused chain; still serve any non-member controllers.
        from rayfed_tpu.fl.fedavg import (
            packed_quantized_sum,
            packed_weighted_sum,
        )

        try:
            value = objs[0].get_local_ref().resolve(timeout=backstop)
            if quant is not None:
                result = packed_quantized_sum(
                    [_to_wire(value)], weights, out_dtype=out_dtype,
                    ref=qref,
                )
            else:
                result = packed_weighted_sum(
                    [value], weights, out_dtype=out_dtype
                )
            if non_members:
                _broadcast_non_members(result)
                _release_non_members()
        except BaseException as exc:
            _poison_ring_edges(
                runtime, exc, ring=ring, m=0, my_idx=my_idx,
                rs_id=rs_id, ag_id=ag_id, commit_id=commit_id,
                release_id=release_id, nm_id=nm_id,
                non_members=non_members,
            )
            # Same contract as the main path: the poison unparks any
            # non-member controllers, but an interrupt must stop the
            # caller unwrapped.
            _quant_rollback()
            if isinstance(exc, (KeyboardInterrupt, SystemExit)):
                raise
            RING_STATS["rounds_aborted"] += 1
            if isinstance(exc, RingRoundError):
                raise
            raise RingRoundError(f"ring round aborted: {exc!r}") from exc
        _quant_commit()
        RING_STATS["rounds_completed"] += 1
        return result

    # Everything below may touch the wire: failures poison every key
    # this party was going to produce, then surface as RingRoundError.
    pending_cancels: List[tuple] = []
    agg = None
    try:
        _maybe_fault("local")
        my_packed = objs[my_idx].get_local_ref().resolve(timeout=backstop)
        from rayfed_tpu.fl.compression import PackedTree, PackSpec

        if not isinstance(my_packed, PackedTree):
            raise TypeError(
                "ring_aggregate consumes PackedTree contributions, got "
                f"{type(my_packed).__name__} — produce updates with "
                "fl.compress(tree, packed=True)"
            )
        if quant is not None:
            if int(chunk_elems) != quant.chunk_elems:
                raise ValueError(
                    f"ring chunk grid ({chunk_elems} elems) must match "
                    f"the quantization grid ({quant.chunk_elems}) — "
                    f"both ARE the canonical packed_block_grid chunking"
                )
            my_packed = _to_wire(my_packed)
        buf = np.asarray(my_packed.buf).reshape(-1)
        if buf.size == 0:
            raise ValueError(
                "ring_aggregate needs float leaves to stripe; use "
                "fl.aggregate for passthrough-only trees"
            )
        wire_dt = buf.dtype
        total_elems = int(buf.size)
        nblocks = packed_block_grid(total_elems, chunk_elems)
        stripes = packed_stripe_schedule(nblocks, n)
        # Compressed-domain output defaults to f32 — what every party
        # RETURNS.  (The gather hop re-codes the finalized stripes on
        # the shared round grid as a pure wire encoding — see the
        # all-gather phase below — but every controller decodes back
        # to this dtype, owner included.)
        out_dt = (
            np.dtype(out_dtype) if out_dtype is not None
            else (np.dtype(np.float32) if quant is not None else wire_dt)
        )
        q_fp = None if quant is None else quant.fingerprint()

        def elems(k: int) -> int:
            return _stripe_elems(
                stripes[k], chunk_elems, nblocks, total_elems
            )

        # -- reduce-scatter: my contribution out, my stripe folded in --
        my_stripe_elems = elems(m)
        if my_stripe_elems:
            rs_want = {
                "s": m, "n": n, "nb": nblocks, "el": total_elems,
                "dt": wire_dt.name, "ph": "rs",
            }
            if q_fp is not None:
                # Integer codes mean nothing without the grid: prove
                # both ends derived the identical one before any fold.
                rs_want["qg"] = q_fp
            agg = _make_stripe_agg(
                runtime, len(objs), weights,
                out_dt.name if quant is not None else out_dtype,
                my_stripe_elems,
                chunk_elems, label=f"stripe {m}",
                meta_check=lambda v: _check_meta(v, rs_want),
                quant=quant, quant_blocks=stripes[m],
                # This owner's stripe-compacted slice of the shared
                # reference — its finalize adds back exactly the
                # elements its blocks cover.
                quant_ref=(
                    None if qref is None else _stripe_slice(
                        qref, stripes[m], chunk_elems, total_elems
                    )
                ),
            )
            entries = []
            for i, obj in enumerate(objs):
                if i == my_idx:
                    continue
                entries.append(
                    (obj.get_party(), f"{rs_id}.rs.{i}.{m}", rs_id,
                     agg.sink(i))
                )
                pending_cancels.append((f"{rs_id}.rs.{i}.{m}", rs_id))
            # One loop hop demuxes all N-1 contribution streams.
            transport.recv_stream_many(entries)

        _maybe_fault("rs")
        rs_refs = []
        for k in range(n):
            if k == m or not elems(k):
                continue
            payload: Dict[str, Any] = {
                "data": _stripe_slice(
                    buf, stripes[k], chunk_elems, total_elems
                ),
                "rsm": json.dumps(
                    make_stripe_meta(
                        k, n, nblocks, total_elems, wire_dt.name, "rs",
                        qgrid_fp=q_fp,
                    ),
                    sort_keys=True,
                ),
            }
            if k == 0 and my_packed.passthrough:
                # Non-float leaves ride to the first stripe's owner,
                # which reduces them once and ships the result with its
                # gathered stripe.
                payload["pt"] = tuple(my_packed.passthrough)
            rs_refs.append(
                (
                    ring[k],
                    f"{rs_id}.rs.{my_idx}.{k}",
                    send_on_runtime(
                        runtime, ring[k], payload,
                        f"{rs_id}.rs.{my_idx}.{k}", rs_id,
                        stream=f"{stream}/rs", round_tag=round_tag,
                        quant_meta=q_descriptor,
                    ),
                )
            )
        if my_stripe_elems:
            agg.add_local(
                my_idx,
                _stripe_slice(buf, stripes[m], chunk_elems, total_elems),
            )
        for dest, up, ref in rs_refs:
            if not ref.resolve(timeout=backstop):
                raise RingRoundError(
                    f"reduce-scatter push {up!r} to {dest!r} failed"
                )
        if timings is not None:
            timings["push_s"] = _time.perf_counter() - t_call0

        _maybe_fault("reduce")
        if my_stripe_elems:
            my_reduced = agg.result(timeout=backstop)
        else:
            my_reduced = np.empty(0, out_dt)
        t_mark = _phase_span(
            "reduce_scatter", t_mark,
            detail={"stripe": m, "parties": n},
        )

        # Reduced passthrough: stripe 0's owner always exists (block 0
        # is always in stripe 0) and holds every party's non-float
        # leaves; reduce with the identical per-leaf semantics as the
        # one-shot path.
        reduced_pt: tuple = ()
        if m == 0 and my_packed.passthrough:
            from rayfed_tpu.fl.fedavg import _reduce_passthrough

            pts: List[tuple] = [()] * len(objs)
            pts[my_idx] = tuple(my_packed.passthrough)
            for i in range(len(objs)):
                if i == my_idx:
                    continue
                val = agg.payload_value(i)
                pts[i] = tuple(val["pt"])
            reduced_pt = tuple(
                _reduce_passthrough(pts, weights, total_w)
            )

        # -- all-gather: reduced stripes travel the ring ---------------
        # Compressed-domain rounds code the gather hop on the SHARED
        # round grid (ROADMAP 2a — the reduce-scatter was already
        # integer, the gather shipped f32): the owner codes its
        # finalized stripe, ships + relays the integer codes, and
        # every party (owner INCLUDED) assembles the decoded codes, so
        # the ring result is byte-identical on every controller and
        # equals the full-buffer recode of the exact aggregate — the
        # ring's analogue of the coordinator path's quantized downlink.
        _maybe_fault("ag")

        def _gather_ctx(k: int):
            rows_s, rows_z = quant.rows(stripes[k])
            ref_slice = (
                None if qref is None
                else _stripe_slice(qref, stripes[k], chunk_elems,
                                   total_elems)
            )
            return rows_s, rows_z, ref_slice

        # The gather wire dtype is a round-wide contract: derived from
        # the GRID alone, never from whether this party happens to own
        # a stripe (a zero-stripe party still validates its peers'
        # coded stripes against it).
        ag_dt_name = (
            quant.wire_dtype if quant is not None else out_dt.name
        )
        if quant is not None and my_stripe_elems:
            rows_s, rows_z, ref_slice = _gather_ctx(m)
            my_codes = code_gather_stripe(
                my_reduced, ref_slice, rows_s, rows_z, chunk_elems,
                quant.wire_dtype,
            )
            my_assembled = decode_gather_stripe(
                my_codes, ref_slice, rows_s, rows_z, chunk_elems, out_dt
            )
        else:
            my_codes = None
            my_assembled = np.asarray(my_reduced)
        gathered: Dict[int, np.ndarray] = {m: my_assembled}
        fwd_refs: List[tuple] = []
        fwd_lock = threading.Lock()

        def _ag_payload(k: int, data: np.ndarray) -> Dict[str, Any]:
            payload = {
                "data": data,
                "rsm": json.dumps(
                    make_stripe_meta(
                        k, n, nblocks, total_elems, ag_dt_name, "ag",
                        qgrid_fp=q_fp,
                    ),
                    sort_keys=True,
                ),
            }
            if k == 0 and reduced_pt:
                payload["pt"] = reduced_pt
            return payload

        def _ag_send(k: int, hop: int, payload: Dict[str, Any]) -> None:
            ref = send_on_runtime(
                runtime, succ, payload, f"{ag_id}.ag.{k}.{hop}", ag_id,
                stream=f"{stream}/ag/{k}", round_tag=round_tag,
            )
            with fwd_lock:
                fwd_refs.append((k, hop, ref))

        if elems(m):
            _ag_send(
                m, 1,
                _ag_payload(
                    m, my_codes if my_codes is not None else gathered[m]
                ),
            )

        collected: Dict[int, Any] = {}
        for k in sorted(
            (k for k in range(n) if k != m and elems(k)),
            key=lambda k: (m - k) % n,
        ):
            hop = (m - k) % n  # how many hops stripe k took to reach me

            def _on_stripe(value, k=k, hop=hop):
                # "el" is the FULL buffer's element count (the grid the
                # stripe indexes into); the stripe's own length follows
                # from the schedule and is re-checked at assembly.
                ag_want = {
                    "s": k, "n": n, "nb": nblocks, "el": total_elems,
                    "dt": ag_dt_name, "ph": "ag",
                }
                if q_fp is not None:
                    # Gather codes mean nothing without the grid —
                    # prove both ends derived the identical one before
                    # any decode (and before the relay hop).
                    ag_want["qg"] = q_fp
                _check_meta(value["rsm"], ag_want)
                if hop + 1 <= n - 1:  # successor is not stripe k's owner
                    _ag_send(k, hop + 1, value)
                return value

            # Forward-on-arrival: the then() runs on the codec pool as
            # each stripe decodes, so relaying stripe k overlaps with
            # stripe k+1 still being on the wire.
            collected[k] = recv_on_runtime(
                runtime, pred, f"{ag_id}.ag.{k}.{hop}", ag_id
            ).then(_on_stripe)

        for k, ref in collected.items():
            value = ref.resolve(timeout=backstop)
            arr = np.asarray(value["data"]).reshape(-1)
            if quant is not None:
                rows_s, rows_z, ref_slice = _gather_ctx(k)
                arr = decode_gather_stripe(
                    arr, ref_slice, rows_s, rows_z, chunk_elems, out_dt
                )
            gathered[k] = arr
            if k == 0 and "pt" in value:
                reduced_pt = tuple(value["pt"])
        with fwd_lock:
            pending_fwd = list(fwd_refs)
        for k, hop, ref in pending_fwd:
            if not ref.resolve(timeout=backstop):
                raise RingRoundError(
                    f"all-gather forward of stripe {k} (hop {hop}) to "
                    f"{succ!r} failed"
                )
        t_mark = _phase_span("all_gather", t_mark)

        # -- assemble the full buffer back onto the chunk grid ---------
        full = np.empty(total_elems, out_dt)
        for k in range(n):
            data = gathered.get(k)
            if data is None or not len(stripes[k]):
                continue
            if data.size != elems(k):
                raise RingRoundError(
                    f"stripe {k} carries {data.size} elements, schedule "
                    f"says {elems(k)}"
                )
            off = 0
            for b in stripes[k]:
                size = min(chunk_elems, total_elems - b * chunk_elems)
                full[b * chunk_elems : b * chunk_elems + size] = (
                    data[off : off + size]
                )
                off += size
        spec = my_packed.spec
        if out_dt.name != spec.wire_dtype:
            spec = PackSpec(spec.entries, spec.treedef, out_dt.name)
        result = PackedTree(full, reduced_pt, spec)

        # Non-member result broadcast rides BEFORE the commit ring:
        # a failed broadcast then aborts the round on every controller
        # (the commit never completes), and non-members only RETURN the
        # result once their release token arrives — lockstep with the
        # members.
        if m == 0 and non_members:
            _broadcast_non_members(result)

        # -- commit ring: agree the round landed everywhere ------------
        _maybe_fault("commit")
        token = {"ok": 1}

        def _token_send(up: str, down) -> None:
            if not send_on_runtime(
                runtime, succ, token, up, down, round_tag=round_tag
            ).resolve(timeout=backstop):
                raise RingRoundError(
                    f"commit token {up!r} to {succ!r} failed"
                )

        if m == 0:
            _token_send(f"{commit_id}.c.1", commit_id)
            recv_on_runtime(
                runtime, pred, f"{commit_id}.c.{n}", commit_id
            ).resolve(timeout=backstop)
            _token_send(f"{release_id}.r.1", release_id)
        else:
            recv_on_runtime(
                runtime, pred, f"{commit_id}.c.{m}", commit_id
            ).resolve(timeout=backstop)
            _token_send(f"{commit_id}.c.{m + 1}", commit_id)
            recv_on_runtime(
                runtime, pred, f"{release_id}.r.{m}", release_id
            ).resolve(timeout=backstop)
            if m < n - 1:
                _token_send(f"{release_id}.r.{m + 1}", release_id)
    except BaseException as exc:
        _quant_rollback()
        for up, down in pending_cancels:
            transport.cancel_stream(up, down)
        _poison_ring_edges(
            runtime, exc, ring=ring, m=m, my_idx=my_idx,
            rs_id=rs_id, ag_id=ag_id, commit_id=commit_id,
            release_id=release_id, nm_id=nm_id, non_members=non_members,
        )
        if isinstance(exc, (KeyboardInterrupt, SystemExit)):
            # The poison still unparks the peers, but an interrupt must
            # STOP the caller — wrapping it as RingRoundError would let
            # the trainer's fallback swallow it and keep training.
            raise
        RING_STATS["rounds_aborted"] += 1
        _telemetry.event(
            "ring.abort", round=round_tag, party=me, outcome="error",
            detail={"error": repr(exc)},
        )
        if isinstance(exc, RingRoundError):
            raise
        raise RingRoundError(f"ring round aborted: {exc!r}") from exc

    if m == 0 and non_members:
        try:
            _release_non_members()
        except Exception:  # pragma: no cover - post-commit best effort
            logger.exception("[%s] non-member release pass failed", me)
    _quant_commit()
    RING_STATS["rounds_completed"] += 1
    _phase_span("commit", t_mark)
    if timings is not None:
        timings.setdefault("push_s", 0.0)
        timings["agg_s"] = _time.perf_counter() - t_call0
    return result


def _make_stripe_agg(runtime, n_sources, weights, out_dtype, expect_elems,
                     chunk_elems, label, meta_check=None, quant=None,
                     quant_blocks=None, quant_ref=None):
    from rayfed_tpu.fl.streaming import StripeAggregator

    return StripeAggregator(
        n_sources,
        weights=weights,
        allowed=runtime.cluster_config.serializing_allowed_list,
        party=runtime.party,
        # The fold grid must match the stripe compaction grid, or an
        # overridden granularity would fold in 4 MB units only (no
        # streaming overlap) and over-allocate the accumulator.
        chunk_elems=chunk_elems,
        out_dtype=out_dtype,
        expect_elems=expect_elems,
        label=label,
        meta_check=meta_check,
        # Compressed-domain rounds: integer codes fold into a donated
        # i32 accumulator; quant_blocks selects this stripe's grid rows
        # for the single fused rescale, quant_ref its compacted
        # reference slice.
        quant=quant,
        quant_blocks=quant_blocks,
        quant_ref=quant_ref,
    )


def _poison_ring_edges(
    runtime, exc, *, ring, m, my_idx, rs_id, ag_id, commit_id, release_id,
    nm_id, non_members,
) -> None:
    """Best-effort poison of every rendezvous key this party produces.

    The receivers' recvs (and stream sinks) then raise the originating
    error within a round-trip instead of parking until the backstop,
    and each of them unwinds its OWN outgoing edges the same way — the
    abort cascades around the ring.  Duplicate poisons of an
    already-consumed key are deduped by the mailbox.
    """
    poison = getattr(runtime.transport, "_send_poison", None)
    if poison is None:  # transport without a poison path (custom proxy)
        return
    n = len(ring)
    succ = ring[(m + 1) % n]
    edges = []
    for k in range(n):  # reduce-scatter pushes I owed stripe owners
        if k != m:
            edges.append((ring[k], f"{rs_id}.rs.{my_idx}.{k}", rs_id))
    for k in range(n):  # all-gather forwards I owed my successor
        hop = (m - k) % n + 1
        if hop <= n - 1:
            edges.append((succ, f"{ag_id}.ag.{k}.{hop}", ag_id))
    edges.append((succ, f"{commit_id}.c.{m + 1}", commit_id))
    if m < n - 1:
        edges.append((succ, f"{release_id}.r.{m + 1}", release_id))
    if m == 0:
        for p in non_members:
            edges.append((p, nm_id, nm_id))
            edges.append((p, f"{release_id}.nm", release_id))
    for dest, up, down in edges:
        if dest == runtime.party:
            continue  # n==1 degenerate ring: succ is this party itself
        try:
            poison(dest, up, down, exc)
        except Exception:  # pragma: no cover - best effort
            logger.exception(
                "[%s] failed to poison ring edge (%s, %s) at %s",
                runtime.party, up, down, dest,
            )
