"""Buffered asynchronous federated rounds with EXACT integer staleness
decay — the round barrier removed without giving up the bit-exactness
contracts the synchronous stack is built on.

The synchronous loop (fl.trainer / fl.streaming) admits one global
round clock: every party contributes to round ``r`` and the slowest
party sets the round's wall.  The quorum layer (fl.quorum) trims the
tail by CUTTING stragglers; this module keeps them.  Parties push a
staleness-tagged quantized delta whenever they finish local work; the
coordinator folds each arrival into a RUNNING donated-i32 code buffer
through the UNCHANGED :func:`fl.fedavg.quantized_accum_kernel` and
emits a new model **version** every K contributions (``buffer_k``) or
T seconds (``flush_s``) — FedBuff's buffered-async regime (Nguyen et
al., arXiv:2106.06639) run entirely in the compressed domain.

Exactness (why the buffer can fold arrivals in ANY order)
---------------------------------------------------------

A contribution coded on the version-``v`` grid arrives with staleness
``s = v_now − v``.  Staleness-decayed weighting is applied as an
INTEGER SHIFT::

    w_eff = w >> min(s, staleness_cap)

so the folded term stays ``w_eff · q`` with ``w_eff`` a non-negative
integer — exactly the contract of the i32 fold.  Integer adds commute
and associate with no rounding, hence for one version's contribution
set the running buffer holds ``Σ_p w_eff_p · q_p`` REGARDLESS of
arrival order, and the single fused rescale
(:func:`fl.fedavg.finalize_packed_quantized`) emits bytes identical to
a sorted-order refold of the same set through
:func:`fl.fedavg.packed_quantized_sum` at weights ``w_eff`` — the same
cutoff-refold contract the quorum layer pins one level up, now per
model version.  A multiplicative float decay (``w · α^s``) would break
both the exactness and the i32 overflow bound; the shift keeps the
headroom guard (:meth:`fl.quantize.QuantGrid.check_weight_headroom`)
sufficient as stated.

The staleness recurrence at per-party staleness
-----------------------------------------------

This is the asynchronous end of the unified staleness recurrence
derived in :mod:`fl.overlap` (one-round staleness: the pipelined
runner).  There, every party is exactly one round stale and the DGA
correction makes the corrected contribution's delta equal the party's
raw local displacement, so the round grid and the accelerated server
step both consume one-round-stale displacements.  Here staleness is
per-party and unbounded, so the correction moves from algebra to
weighting: a version-``v`` contribution decodes against the version-
``v`` reference it was coded on (every broadcast ships its grid, so
the codes are always attributable), re-codes onto the CURRENT grid
through the shared :class:`fl.quantize.RoundCodec`, and folds at the
shift-decayed weight.  The server step (fl.server_opt), when
configured, consumes the buffered mean exactly as the synchronous loop
does — the FedAC delayed-gradient analysis (arXiv:2006.08950) is what
bounds the staleness penalty the decay is tuned against.

Version-tagged wire contract
----------------------------

Broadcasts and contributions stamp the model version into ordinary
frame metadata under :data:`rayfed_tpu.transport.wire
.ASYNC_VERSION_KEY` (``TransportManager.send(version_tag=...)``) — a
new metadata KEY, not a frame-layout change, fingerprinted by
``tool/check_wire_format.py`` like every cross-party contract.  The
version-0 bootstrap needs no negotiation: every controller derives the
identical ``mode="abs"`` grid from the initial params it already
holds (:func:`bootstrap_grid` — same pure-numpy derivation as the
synchronous loop's grids), and every later grid rides the broadcast
payload itself.  Rosters ride epoch tags: a party's final push
(``fin``) retires it from the roster and bumps the epoch stamped on
subsequent broadcasts.

When NOT to go async (see docs/source/async_rounds.rst): homogeneous
fleets (the buffer only re-derives the synchronous round at extra
version churn), secure aggregation (pairwise masks are keyed by a
synchronous round tuple — no per-arrival fold can unmask), and
workloads needing every party represented in every emitted model
(async emission is a weighted SAMPLE of the fleet per version).
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from rayfed_tpu import chaos, telemetry
from rayfed_tpu.fl import quantize as qz
from rayfed_tpu.fl.compression import PackedTree, PackSpec, pack_tree
from rayfed_tpu.fl.fedavg import (
    finalize_packed_quantized,
    quantized_accum_kernel,
)
from rayfed_tpu.fl.quantize import (
    QuantGrid,
    QuantizedPackedTree,
    RoundCodec,
    grid_descriptor,
    make_round_grid,
)

logger = logging.getLogger(__name__)

#: Shift cap: beyond this staleness every weight decays identically
#: (``w >> cap``) — and a unit weight has already decayed to zero at
#: shift 1, so the cap mostly bounds the grid-retention window.
DEFAULT_STALENESS_CAP = 8

#: Contributions buffered per emitted model version (FedBuff's K).
DEFAULT_BUFFER_K = 4

# Per-process counters surfaced by fed.metrics_snapshot() under the
# "async" section of metrics.METRICS_SCHEMA (the quorum/ring pattern:
# the driver lives per process, not on the transport).
ASYNC_STATS: Dict[str, Any] = {
    "versions_emitted": 0,
    "folds": 0,
    "buffer_occupancy": 0,
    "staleness_hist": {},
    "decay_shift_total": 0,
    "dropped_decayed_out": 0,
    "dropped_unretained": 0,
    "recoded_stale": 0,
}


def reset_async_stats() -> None:
    """Zero the per-process async counters (tests / bench sections)."""
    ASYNC_STATS.update(
        versions_emitted=0, folds=0, buffer_occupancy=0,
        staleness_hist={}, decay_shift_total=0, dropped_decayed_out=0,
        dropped_unretained=0, recoded_stale=0,
    )


def decay_weight(weight: int, staleness: int,
                 staleness_cap: int = DEFAULT_STALENESS_CAP) -> int:
    """The exact integer staleness decay: ``w >> min(s, cap)``.

    ONE producer for driver, tests and docs — the whole exactness
    argument rests on the decayed weight staying a non-negative
    integer, so the decay must never be reimplemented as a float
    multiply at a call site.
    """
    w = int(weight)
    s = int(staleness)
    if w < 0 or float(weight) != w:
        raise ValueError(
            f"compressed-domain folds need non-negative integral "
            f"weights (example counts), got {weight!r}"
        )
    if s < 0:
        raise ValueError(
            f"staleness is versions-behind, never negative (got {s}) — "
            f"a contribution cannot be coded against an unemitted model"
        )
    return w >> min(s, int(staleness_cap))


def bootstrap_grid(model_buf: Any, wire_dtype: str = "uint8",
                   chunk_elems: Optional[int] = None) -> QuantGrid:
    """The version-0 grid: ``mode="abs"`` over the initial params.

    Before the first version there is no observed delta to range a
    delta grid (the synchronous loop's bootstrap runs round 0
    unquantized instead — an async buffer cannot, the running fold IS
    integer).  An abs-mode grid over the initial model codes the
    version-0 contributions themselves; every controller derives it
    from the bit-identical initial params, so like every round grid the
    derivation IS the negotiation (fingerprint-checked on each frame).
    From version 1 on the coordinator rotates to delta grids ranged by
    the observed version delta, shipped on the broadcast payload.
    """
    if isinstance(model_buf, PackedTree):
        model_buf = model_buf.buf
    flat = np.asarray(model_buf).reshape(-1).astype(np.float32)
    if flat.size and float(flat.max() - flat.min()) == 0.0:
        # An all-constant init (all-zeros is the classic) ranges every
        # chunk to the eps floor: every version-0 contribution clips
        # to the constant, the first emitted delta is exactly zero,
        # and the zero-delta guard then reuses this grid forever — the
        # fleet is silently stuck at the init.  Loud, at derivation.
        raise ValueError(
            "bootstrap_grid: initial params are all-constant — the "
            "version-0 abs grid ranges over the initial value spread, "
            "so a constant init clips every contribution to itself "
            "(randomize the init, as real models do)"
        )
    return make_round_grid(
        flat, chunk_elems=chunk_elems, wire_dtype=wire_dtype,
        mode="abs",
    )


class AsyncBuffer:
    """The RUNNING compressed-domain fold for one model version.

    Holds a donated-i32 accumulator over the grid's padded block
    layout and folds each arrival with ONE call of the unchanged
    :func:`fl.fedavg.quantized_accum_kernel` (chunk = the whole padded
    buffer, offset 0 — the same donated widening multiply-add the
    streaming aggregator chains per chunk).  :meth:`finalize` is the
    same single fused rescale every synchronous topology ends in, so
    the emitted bytes are identical to a sorted-order
    :func:`fl.fedavg.packed_quantized_sum` refold of the folded
    ``(codes, w_eff)`` set — the buffered fold is order-free by
    integer arithmetic, not by tolerance.
    """

    __slots__ = ("grid", "ref", "staleness_cap", "_acc", "_kernel",
                 "_padded", "_template", "_count", "_total_w",
                 "staleness_hist", "decay_shift_total")

    def __init__(self, grid: QuantGrid, ref: Optional[np.ndarray],
                 template: PackedTree,
                 staleness_cap: int = DEFAULT_STALENESS_CAP) -> None:
        import jax.numpy as jnp

        self.staleness_cap = int(staleness_cap)
        # Tree skeleton for the finalized PackedTree (entries/treedef/
        # passthrough); the fold itself never looks at it.
        self._template = template
        self._padded = 0
        self._kernel = None
        self._acc = None
        self.grid = grid
        self.ref = None
        self.staleness_hist: Dict[int, int] = {}
        self.decay_shift_total = 0
        self._count = 0
        self._total_w = 0
        self.reset(grid, ref)
        del jnp  # imported eagerly so reset() never pays first-import

    @property
    def occupancy(self) -> int:
        """Contributions folded into the current (unemitted) version."""
        return self._count

    @property
    def total_weight(self) -> int:
        return self._total_w

    def reset(self, grid: QuantGrid, ref: Optional[np.ndarray]) -> None:
        """Start the next version's buffer on (possibly rotated) grid.

        Rotation never changes the packed layout — the padded
        accumulator and the cached kernel survive grid swaps; only the
        scales/zps/reference move.
        """
        import jax.numpy as jnp

        if self._acc is not None and (
            grid.total_elems != self.grid.total_elems
            or grid.chunk_elems != self.grid.chunk_elems
        ):
            raise ValueError(
                f"grid rotation changed the packed layout "
                f"({self.grid.total_elems}/{self.grid.chunk_elems} -> "
                f"{grid.total_elems}/{grid.chunk_elems}) — the running "
                f"buffer is per-model-layout; build a new AsyncBuffer "
                f"when the model structure changes"
            )
        self.grid = grid
        if ref is not None:
            ref = np.asarray(ref).reshape(-1).astype(np.float32)
            if int(ref.size) != grid.total_elems:
                raise ValueError(
                    f"reference has {ref.size} elements, grid covers "
                    f"{grid.total_elems}"
                )
        elif grid.mode == "delta":
            raise ValueError(
                "delta-mode grids fold codes of x - ref: pass the "
                "version's shared reference buffer"
            )
        self.ref = ref
        self._padded = grid.nblocks * grid.chunk_elems
        self._kernel = quantized_accum_kernel(
            self._padded, grid.wire_dtype
        )
        self._acc = jnp.zeros(self._padded, jnp.int32)
        self._count = 0
        self._total_w = 0
        self.staleness_hist = {}
        self.decay_shift_total = 0
        ASYNC_STATS["buffer_occupancy"] = 0

    def fold(self, qt: QuantizedPackedTree, weight: int = 1,
             staleness: int = 0) -> int:
        """Fold one arrival; returns the effective (decayed) weight.

        Returns 0 — and folds NOTHING — when the shift decays the
        weight away entirely (the contribution is too stale to move the
        average by even one integer count).  Raises when the codes were
        taken on a different grid: stale codes must re-code through the
        shared :class:`fl.quantize.RoundCodec` first (the coordinator
        driver does; see :func:`run_async_coordinator`).
        """
        import jax.numpy as jnp

        if not isinstance(qt, QuantizedPackedTree):
            raise TypeError(
                f"AsyncBuffer folds QuantizedPackedTree contributions, "
                f"got {type(qt).__name__}"
            )
        if qt.gmeta != self.grid.meta():
            raise ValueError(
                f"contribution was coded on a different grid "
                f"(fp={qt.gmeta.fp:#010x} vs "
                f"{self.grid.fingerprint():#010x}) — version-stale "
                f"codes re-code through the shared RoundCodec before "
                f"the fold"
            )
        shift = min(int(staleness), self.staleness_cap)
        w_eff = decay_weight(weight, staleness, self.staleness_cap)
        self.staleness_hist[shift] = self.staleness_hist.get(shift, 0) + 1
        hist = ASYNC_STATS["staleness_hist"]
        hist[shift] = hist.get(shift, 0) + 1
        if w_eff <= 0:
            ASYNC_STATS["dropped_decayed_out"] += 1
            return 0
        # Overflow guard BEFORE touching the accumulator: a rejected
        # fold must leave the buffer exactly as it was.
        self.grid.check_weight_headroom(self._total_w + w_eff)
        codes = np.asarray(qt.buf).reshape(-1)
        if codes.size != self.grid.total_elems:
            raise ValueError(
                f"contribution carries {codes.size} codes, grid covers "
                f"{self.grid.total_elems}"
            )
        if codes.size != self._padded:
            # Pad onto the canonical block grid; the finalize slices
            # back to total_elems, so the pad value never reaches the
            # output — zeros keep the padded adds trivially exact.
            padded = np.zeros(self._padded, codes.dtype)
            padded[: codes.size] = codes
            codes = padded
        self._acc = self._kernel(
            self._acc, jnp.asarray(codes), 0, w_eff
        )
        self._count += 1
        self._total_w += w_eff
        self.decay_shift_total += shift
        ASYNC_STATS["folds"] += 1
        ASYNC_STATS["buffer_occupancy"] = self._count
        ASYNC_STATS["decay_shift_total"] += shift
        return w_eff

    def finalize(self, out_dtype: Any = np.float32) -> PackedTree:
        """The buffered version's weighted mean — ONE fused rescale
        (:func:`fl.fedavg.finalize_packed_quantized`), byte-identical
        to the sorted-order ``packed_quantized_sum`` refold of the
        folded set.  The buffer stays live; call :meth:`reset` to
        start the next version."""
        if self._count == 0:
            raise ValueError(
                "finalize on an empty buffer — the weighted average of "
                "no contributions is undefined (emission is gated on "
                "occupancy for exactly this reason)"
            )
        buf = finalize_packed_quantized(
            self._acc, self.grid.scales, self.grid.zps,
            float(self._total_w), self.grid.total_elems,
            self.grid.chunk_elems, out_dtype, ref=self.ref,
        )
        tmpl = self._template
        spec = PackSpec(
            tmpl.spec.entries, tmpl.spec.treedef,
            np.dtype(out_dtype).name,
        )
        # finalize_packed_quantized consumed nothing (acc is not
        # donated there) — but the NEXT fold's donation would invalidate
        # the view finalize returned lazily; materialization happens at
        # reset() via the fresh zeros, so no copy is needed here.
        return PackedTree(buf, tmpl.passthrough, spec)


def _wrap_server_opt(server_opt: Any) -> Any:
    if server_opt is None or hasattr(server_opt, "step_fn"):
        return server_opt
    from rayfed_tpu.fl.server_opt import PackedServerOptimizer

    return PackedServerOptimizer(server_opt)


def run_async_coordinator(
    mgr: Any,
    party: str,
    members: Sequence[str],
    params: Any,
    *,
    cycles: Any,
    buffer_k: int = DEFAULT_BUFFER_K,
    flush_s: Optional[float] = None,
    wire_quant: str = "uint8",
    chunk_elems: Optional[int] = None,
    staleness_cap: int = DEFAULT_STALENESS_CAP,
    grid_retention: Optional[int] = None,
    server_opt: Any = None,
    stream: str = "async",
    timeout_s: Optional[float] = None,
    version_log: Optional[List[Dict[str, Any]]] = None,
    record_folds: Optional[List[Dict[str, Any]]] = None,
) -> Dict[str, Any]:
    """The buffered-async coordinator over a bare TransportManager.

    Parks one receive per active member and multiplexes arrivals
    through a queue; each arrival folds into the running
    :class:`AsyncBuffer` (re-coding through the shared
    :class:`fl.quantize.RoundCodec` when its version's grid has
    rotated), and a new model version emits every ``buffer_k``
    contributions or — evaluated at arrival time — ``flush_s`` seconds
    (T-second emission is an arrival-driven check on purpose: an empty
    buffer has nothing to emit, so a timer thread would only ever fire
    into the same gate).  The reply to each push carries the CURRENT
    model, its grid and its version (``version_tag`` frame metadata):
    the reply leg IS the version broadcast, so a party is never more
    than one push behind discovering a new version.

    ``cycles``: pushes expected per member (int, or dict keyed by
    member — heterogeneous counts are roster churn: a member's final
    push retires it and bumps the epoch tag).  ``grid_retention``: how
    many historical versions' (grid, reference) pairs stay decodable;
    older arrivals are dropped-with-counter (their shift-decayed
    weight is ≤ ``w >> staleness_cap`` anyway).  ``record_folds``
    (tests): appends ``{version, party, qt, weight, w_eff,
    staleness}`` per fold — the refold oracle's input.
    """
    import jax.numpy as jnp

    members = [str(m) for m in members]
    if isinstance(cycles, int):
        expected = {m: int(cycles) for m in members}
    else:
        expected = {m: int(cycles[m]) for m in members}
    total_pushes = sum(expected.values())
    retention = (
        int(grid_retention) if grid_retention is not None
        else int(staleness_cap) + 2
    )
    sopt = _wrap_server_opt(server_opt)

    tmpl = pack_tree(params, jnp.float32)
    model = np.asarray(tmpl.buf).astype(np.float32)
    grid0 = bootstrap_grid(model, wire_quant, chunk_elems)
    # version -> (grid, reference) for decode of version-stale codes.
    grids: Dict[int, Any] = {0: (grid0, None)}
    version = 0
    epoch = 0
    buf = AsyncBuffer(grid0, None, tmpl, staleness_cap=staleness_cap)
    last_emit = time.perf_counter()
    emitted_folds = 0

    arrivals: "queue.Queue" = queue.Queue()

    def _park(member: str, cycle: int) -> None:
        ref = mgr.recv(member, f"{stream}.up.{member}", str(cycle))
        ref.add_done_callback(
            lambda r, _m=member, _c=cycle: arrivals.put((_m, _c, r))
        )

    roster = {m for m in members if expected[m] > 0}
    for m in roster:
        _park(m, 0)

    def _emit_version() -> None:
        nonlocal version, model, last_emit, emitted_folds
        folds = buf.occupancy
        total_w = buf.total_weight
        hist = dict(buf.staleness_hist)
        shifts = buf.decay_shift_total
        with telemetry.span(
            "async.version", party=party, stream=stream,
            round=version + 1, epoch=epoch,
            detail={"folds": folds, "total_weight": total_w,
                    "decay_shift_total": shifts},
        ):
            agg = buf.finalize(np.float32)
            if sopt is not None:
                sopt.ensure(model)
                agg = sopt.step_fn(model)(agg)
                sopt.resync(model, np.asarray(agg.buf))
            new_model = np.asarray(agg.buf).astype(np.float32)
            delta = new_model - model
            if np.any(delta):
                new_grid = make_round_grid(
                    delta, chunk_elems=grid0.chunk_elems,
                    wire_dtype=wire_quant, mode="delta",
                    expand=qz.QUANT_DELTA_EXPAND,
                )
                new_ref: Optional[np.ndarray] = new_model
            else:
                # Degenerate no-movement version: keep the grid (and
                # its reference) — rotating onto an all-zero delta
                # range would produce a clip-everything grid.
                new_grid, new_ref = grids[version]
            version += 1
            grids[version] = (new_grid, new_ref)
            for old in [v for v in grids if v < version - retention]:
                del grids[old]
            model = new_model
            buf.reset(new_grid, new_ref)
        ASYNC_STATS["versions_emitted"] += 1
        emitted_folds += folds
        if version_log is not None:
            version_log.append({
                "version": version, "folds": folds,
                "total_weight": total_w, "staleness_hist": hist,
                "decay_shift_total": shifts,
                "model": model.copy(),
                # Wall-clock emission stamp: time-to-target-loss curves
                # (bench) read it; refold oracles ignore it.
                "t_wall": time.time(),
            })
        last_emit = time.perf_counter()

    processed = 0
    while processed < total_pushes:
        member, cycle, ref = arrivals.get()
        payload = ref.resolve(timeout_s)
        processed += 1
        qt = payload["qt"]
        v_from = int(payload["v"])
        weight = int(payload["weight"])
        staleness = version - v_from
        # Version rides the round tag (the async analogue of a round:
        # trace_report's per-round pages become per-version pages) and
        # the staleness attribution rides detail — tool/trace_report.py
        # aggregates it into the staleness report.  The detail dict is
        # filled in as the fold resolves (the span emits at exit).
        fold_detail: Dict[str, Any] = {
            "staleness": staleness, "cycle": cycle,
            "v_from": v_from, "weight": weight,
        }
        with telemetry.span(
            "async.fold", party=party, peer=member, stream=stream,
            round=version, epoch=epoch, detail=fold_detail,
        ):
            held = grids.get(v_from)
            if held is None:
                # Beyond the retention window the reference needed to
                # decode is gone; the shift-decayed weight out there is
                # negligible by construction — drop loudly.
                ASYNC_STATS["dropped_unretained"] += 1
                logger.warning(
                    "[%s] dropping contribution from %s coded at "
                    "version %d (current %d, retention %d)",
                    party, member, v_from, version, retention,
                )
                w_eff = 0
            else:
                if v_from != version:
                    g_old, ref_old = held
                    if qt.gmeta != g_old.meta():
                        raise ValueError(
                            f"contribution from {member} claims "
                            f"version {v_from} but its codes carry "
                            f"grid fp={qt.gmeta.fp:#010x}, version "
                            f"{v_from}'s grid is "
                            f"{g_old.fingerprint():#010x}"
                        )
                    decoded = qt.dequantize(np.float32, ref=ref_old)
                    codec = RoundCodec(buf.grid, buf.ref)
                    qt = codec.to_wire(decoded)
                    ASYNC_STATS["recoded_stale"] += 1
                    fold_detail["recoded"] = True
                w_eff = buf.fold(qt, weight, staleness)
                fold_detail["w_eff"] = w_eff
                if record_folds is not None:
                    record_folds.append({
                        "version": version, "party": member,
                        "qt": qt, "weight": weight, "w_eff": w_eff,
                        "staleness": staleness,
                    })
        now = time.perf_counter()
        if buf.occupancy and (
            buf.occupancy >= int(buffer_k)
            or (flush_s is not None and now - last_emit >= flush_s)
        ):
            _emit_version()
        cur_grid, _cur_ref = grids[version]
        mgr.send(
            member,
            {
                "v": version,
                "buf": model,
                "scales": cur_grid.scales,
                "zps": cur_grid.zps,
                "mode": cur_grid.mode,
                "epoch": epoch,
            },
            f"{stream}.dn.{member}", str(cycle),
            stream=stream, version_tag=version, epoch_tag=epoch,
            quant_meta=grid_descriptor(cur_grid),
        )
        if bool(payload.get("fin")) or cycle + 1 >= expected[member]:
            roster.discard(member)
            epoch += 1
            telemetry.event(
                "async.roster", party=party, peer=member,
                stream=stream, epoch=epoch, round=version,
            )
        else:
            _park(member, cycle + 1)

    # Residue: arrivals that landed after the last emission still owe
    # the fleet a version (every contribution reaches some model).
    if buf.occupancy:
        _emit_version()
    return {
        "w": model,
        "versions": version,
        "epoch": epoch,
        "folds": emitted_folds,
        "template": tmpl,
    }


def run_async_party(
    mgr: Any,
    party: str,
    coordinator: str,
    params: Any,
    local_step_fn: Callable[[str, PackedTree, int, int], PackedTree],
    *,
    cycles: int,
    weight: int = 1,
    wire_quant: str = "uint8",
    chunk_elems: Optional[int] = None,
    stream: str = "async",
    timeout_s: Optional[float] = None,
) -> Dict[str, Any]:
    """One virtual party's push loop (no round barrier anywhere).

    Each cycle: run ``local_step_fn(party, packed_model, version,
    cycle) -> PackedTree`` (its measured duration feeds the chaos
    ``local_step`` hook — a seeded ``local_slowdown`` schedule turns a
    homogeneous in-process fleet into a deterministic 2-10x straggler
    spread), code the result on the CURRENT version's grid through the
    party's error-feedback :class:`fl.quantize.RoundCodec`, push it
    version-tagged, and adopt whatever model version the reply carries.
    The party never waits for any other party — only for its own
    reply, which the coordinator sends immediately after folding.
    """
    import jax.numpy as jnp

    tmpl = pack_tree(params, jnp.float32)
    model = np.asarray(tmpl.buf).astype(np.float32)
    grid = bootstrap_grid(model, wire_quant, chunk_elems)
    gref: Optional[np.ndarray] = None
    version = 0
    f32_spec = PackSpec(tmpl.spec.entries, tmpl.spec.treedef, "float32")
    packed = PackedTree(model, tmpl.passthrough, f32_spec)
    scope = f"{stream}.{party}"

    for c in range(int(cycles)):
        t_wall = time.time()
        t0 = time.perf_counter()
        contrib = local_step_fn(party, packed, version, c)
        dur = time.perf_counter() - t0
        telemetry.emit(
            "async.local", t_start=t_wall, dur_s=dur, party=party,
            stream=stream, round=version, detail={"cycle": c},
        )
        # The chaos hook may SLEEP here (local_slowdown multiplier over
        # the measured baseline) — that stall is exactly the
        # heterogeneous-device time the async buffer absorbs.
        chaos.fire(
            "local_step", party, version=version, cycle=c,
            baseline_s=dur,
        )
        codec = RoundCodec(grid, gref, scope=scope)
        qt = codec.to_wire(contrib)
        with telemetry.span(
            "async.cycle", party=party, stream=stream,
            round=version, detail={"cycle": c},
        ):
            mgr.send(
                coordinator,
                {
                    "v": version,
                    "cycle": c,
                    "weight": int(weight),
                    "fin": c + 1 >= int(cycles),
                    "qt": qt,
                },
                f"{stream}.up.{party}", str(c),
                stream=stream, version_tag=version,
                quant_meta=codec.descriptor,
            )
            reply = mgr.recv(
                coordinator, f"{stream}.dn.{party}", str(c)
            ).resolve(timeout_s)
        # The fold always lands (the coordinator replies after it) —
        # commit the pending error-feedback residual.
        codec.commit()
        rv = int(reply["v"])
        if rv != version:
            version = rv
            model = np.asarray(reply["buf"]).astype(np.float32)
            mode = str(reply["mode"])
            grid = QuantGrid(
                np.asarray(reply["scales"]), np.asarray(reply["zps"]),
                grid.chunk_elems, grid.total_elems, wire_quant, mode,
            )
            gref = model if mode == "delta" else None
            packed = PackedTree(model, tmpl.passthrough, f32_spec)
    return {"w": model, "version": version}


def run_async_fleet(
    parties: Sequence[str],
    params: Any,
    local_step_fn: Callable[[str, PackedTree, int, int], PackedTree],
    *,
    cycles: Any = 4,
    weights: Optional[Dict[str, int]] = None,
    buffer_k: int = DEFAULT_BUFFER_K,
    flush_s: Optional[float] = None,
    wire_quant: str = "uint8",
    chunk_elems: Optional[int] = None,
    staleness_cap: int = DEFAULT_STALENESS_CAP,
    grid_retention: Optional[int] = None,
    server_opt: Any = None,
    stream: str = "async",
    timeout_s: float = 300.0,
    version_log: Optional[List[Dict[str, Any]]] = None,
    record_folds: Optional[List[Dict[str, Any]]] = None,
) -> Dict[str, Any]:
    """In-process virtual-party harness: N loopback TransportManagers
    (local-link auto-upgrade), one thread per party, the first name
    coordinating — the in-process topology of the hierarchy tests,
    packaged so every test drives the identical fleet instead of a
    hand-rolled copy.  No party subprocesses, by design: the tier-1
    budget rides in-process fleets (ISSUE 20 satellite 6).
    """
    import socket

    from rayfed_tpu.config import ClusterConfig, JobConfig, PartyConfig
    from rayfed_tpu.transport.manager import TransportManager

    parties = [str(p) for p in parties]
    if len(parties) < 2:
        raise ValueError("an async fleet needs a coordinator + >= 1 member")
    coordinator, members = parties[0], parties[1:]

    socks = [socket.socket() for _ in parties]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = {p: s.getsockname()[1] for p, s in zip(parties, socks)}
    for s in socks:
        s.close()

    def _mk(p: str) -> Any:
        cc = ClusterConfig(
            parties={
                q: PartyConfig.from_dict(
                    {"address": f"127.0.0.1:{ports[q]}"}
                )
                for q in parties
            },
            current_party=p,
        )
        return TransportManager(
            cc,
            JobConfig(
                device_put_received=False,
                zero_copy_host_arrays=True,
                local_link="auto",
            ),
        )

    mgrs = {p: _mk(p) for p in parties}
    results: Dict[str, Any] = {}
    errors: Dict[str, BaseException] = {}
    try:
        for m in mgrs.values():
            m.start()

        def _coord() -> None:
            try:
                results[coordinator] = run_async_coordinator(
                    mgrs[coordinator], coordinator, members, params,
                    cycles=cycles, buffer_k=buffer_k, flush_s=flush_s,
                    wire_quant=wire_quant, chunk_elems=chunk_elems,
                    staleness_cap=staleness_cap,
                    grid_retention=grid_retention,
                    server_opt=server_opt, stream=stream,
                    timeout_s=timeout_s, version_log=version_log,
                    record_folds=record_folds,
                )
            # fedlint: disable=FED004 — transferred, not swallowed: the parent re-raises from the errors dict after join
            except BaseException as e:
                errors[coordinator] = e

        def _member(p: str) -> None:
            try:
                n = cycles if isinstance(cycles, int) else cycles[p]
                results[p] = run_async_party(
                    mgrs[p], p, coordinator, params, local_step_fn,
                    cycles=n,
                    weight=(weights or {}).get(p, 1),
                    wire_quant=wire_quant, chunk_elems=chunk_elems,
                    stream=stream, timeout_s=timeout_s,
                )
            # fedlint: disable=FED004 — transferred, not swallowed: the parent re-raises from the errors dict after join
            except BaseException as e:
                errors[p] = e

        threads = [threading.Thread(target=_coord, daemon=True)] + [
            threading.Thread(target=_member, args=(p,), daemon=True)
            for p in members
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout_s)
        if errors:
            raise RuntimeError(
                f"async fleet failed: "
                f"{ {p: repr(e) for p, e in errors.items()} }"
            )
        if any(t.is_alive() for t in threads):
            raise TimeoutError(
                f"async fleet did not complete within {timeout_s}s"
            )
    finally:
        for m in mgrs.values():
            try:
                m.stop()
            except Exception:  # pragma: no cover
                logger.exception("async fleet manager stop failed")
    out = dict(results[coordinator])
    out["party_results"] = {p: results[p] for p in members}
    return out
