"""Streaming on-device aggregation of PackedTree contributions.

The classic FedAvg receive path serializes: wait for every party's
complete payload → decode N full trees → one monolithic reduce.  At
model scale that parks O(parties × model) bytes on the coordinator and
leaves the devices idle while the wire drains.  Here aggregation is
fused into the receive path (THC-style, arXiv:2302.08545): the transport
surfaces payload bytes **as they land** (``TransportManager.recv_stream``
→ ``TransportServer`` chunk sinks), and a :class:`StreamingAggregator`
casts + accumulates each arriving chunk of the packed wire buffer into a
**donated on-device f32 accumulator** while later chunks are still on
the wire — wire time and decode+reduce time overlap, and the reduce
itself never materializes a list of full trees.  (Delta streams trade
memory for wire on top of this: the transport keeps each peer's last
full payload as the diff base — see the transport docs.)

Determinism contract: floating-point addition is not associative, so the
aggregator applies chunks in **party order per block** — party ``i``'s
block ``b`` is folded in only after parties ``0..i-1`` folded theirs.
Arrival order then only affects scheduling, never the result: the
streamed aggregate is bit-identical to the one-shot fused reduce
(:func:`rayfed_tpu.fl.fedavg.packed_weighted_sum`), which performs the
same zero-init → per-party multiply-add chain → final divide + cast.

Non-float (passthrough) leaves are reduced at finalize time with the
same per-leaf semantics as :func:`~rayfed_tpu.fl.fedavg.tree_average`
(the payloads are retained as zero-copy views, so decoding their
skeletons is cheap) — streamed and one-shot aggregation agree on the
whole tree, not just the packed buffer.

``streaming_aggregate`` is the multi-controller entry point: every party
calls it at the same program point with the same arguments (like
:func:`rayfed_tpu.fl.fedavg.aggregate`); contributions flow to the
coordinator on named delta streams (only changed chunks cross the wire
round-over-round) and the result is broadcast back.
"""

from __future__ import annotations

import functools
import json
import logging
import struct
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

logger = logging.getLogger(__name__)

# Elements folded per accumulate dispatch.  2M elements = one 4 MB
# bf16 wire chunk — matching the transport's chunk size keeps roughly
# one dispatch per arriving chunk.
DEFAULT_CHUNK_ELEMS = 1 << 21

# A sink only wakes the aggregator worker after this many new bytes
# (or on completion) — per-64KB-read notifies would thrash the lock.
_NOTIFY_BYTES = 512 * 1024


@functools.lru_cache(maxsize=None)
def _accum_kernel(chunk_elems: int, acc_dtype: str, wire_dtype: str):
    """One donated-accumulator multiply-add step: acc[off:off+C] += w*x.

    The donated accumulator means no second O(model) buffer per step;
    offsets are traced (one compile per (chunk size, dtypes), not per
    offset).  The per-element op chain — convert, multiply by the traced
    weight, add — is EXACTLY the chain ``packed_weighted_sum`` compiles,
    which is what makes streamed and one-shot aggregation bit-identical.
    """
    import jax
    import jax.numpy as jnp

    acc_dt = jnp.dtype(acc_dtype)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def fed_fold_f32(acc, chunk, off, w):
        seg = jax.lax.dynamic_slice(acc, (off,), (chunk_elems,))
        return jax.lax.dynamic_update_slice(
            acc, seg + w * chunk.astype(acc_dt), (off,)
        )

    return fed_fold_f32


# Finalize (divide + cast) is shared with the one-shot path and the
# ring stripe owners: rayfed_tpu.fl.fedavg.finalize_packed_stripe is
# the single producer of the output bytes.


class _Stream:
    """Receive state of one contribution."""

    __slots__ = (
        "payload", "avail_bytes", "complete", "local_tree", "elems_array",
        "data_start", "data_nbytes", "dtype", "applied_blocks",
        "t_complete", "notified_bytes", "manifest", "error",
    )

    def __init__(self) -> None:
        self.payload: Optional[memoryview] = None
        self.avail_bytes = 0
        self.complete = False
        self.local_tree = None  # coordinator's own PackedTree
        self.elems_array: Optional[np.ndarray] = None  # local fast path
        self.data_start = -1  # byte offset of the packed buffer
        self.data_nbytes = -1
        self.dtype: Optional[np.dtype] = None
        self.applied_blocks = 0
        self.t_complete = 0.0
        self.notified_bytes = 0
        self.manifest: Optional[Dict[str, Any]] = None  # parsed payload manifest
        # Quorum mode only: this stream's own failure (dead source,
        # verification failure) — recorded instead of failing the whole
        # aggregation, as long as the quorum stays reachable.
        self.error: Optional[BaseException] = None


class _StreamSink:
    """Transport-facing adapter: thread-safe, throttled notifies."""

    __slots__ = ("_agg", "_index")

    def __init__(self, agg: "StreamingAggregator", index: int) -> None:
        self._agg = agg
        self._index = index

    def on_bytes(self, view: memoryview, total: int) -> None:
        self._agg._on_bytes(self._index, view, total)

    def on_complete(self, payload) -> None:
        self._agg._on_complete(self._index, payload)

    def on_error(self, err: Any) -> None:
        self._agg._on_error(self._index, err)

    def on_frame_abort(self, corrupt: bool = False) -> None:
        self._agg._on_frame_abort(self._index, corrupt)


class StreamingAggregator:
    """Fold N PackedTree contributions into one as their bytes arrive.

    Usage (coordinator side)::

        agg = StreamingAggregator(n_sources=len(parties), weights=w)
        for i, party in enumerate(parties):
            transport.recv_stream(party, up_id, down_id, agg.sink(i))
        agg.add_local(my_index, my_packed_tree)   # no wire hop for self
        averaged = agg.result(timeout=60)         # PackedTree, wire dtype

    The REDUCE itself holds O(model + chunk): one f32 accumulator, no
    list of decoded per-leaf trees.  Wire-payload residency is separate:
    in-flight payload buffers live until their frame completes, and when
    contributions ride delta streams the transport additionally caches
    each peer's last full payload (bounded LRU) as the diff base — that
    is a deliberate memory-for-wire trade, O(streams × wire payload),
    accounted to the transport, not this reducer.
    """

    def __init__(
        self,
        n_sources: int,
        weights: Optional[Sequence[float]] = None,
        allowed: Optional[Dict[str, Any]] = None,
        chunk_elems: int = DEFAULT_CHUNK_ELEMS,
        out_dtype: Any = None,
        quorum: Optional[int] = None,
        labels: Optional[Sequence[str]] = None,
        quant: Optional[Any] = None,
        quant_ref: Optional[Any] = None,
        masked: bool = False,
        mask_recovery: Optional[Any] = None,
        presummed: Optional[str] = None,
        party: Optional[str] = None,
    ) -> None:
        if n_sources < 1:
            raise ValueError("streaming aggregation needs >= 1 source")
        # Acting party for flight-recorder spans (agg.fold/finalize,
        # quorum.cutoff).  In-process multi-party runs share ONE
        # process-global recorder, so an unstamped record would be
        # served by EVERY manager's trace window and the merged
        # timeline would duplicate it under each party's clock offset.
        self._party = None if party is None else str(party)
        # The fold worker allocates the accumulator and dispatches the
        # fold/finalize kernels: it runs bound to the constructing
        # party's runtime so they land on THAT party's device.
        from rayfed_tpu.runtime import get_runtime_or_none

        self._runtime = get_runtime_or_none()
        if quorum is not None and not 1 <= int(quorum) <= n_sources:
            raise ValueError(
                f"quorum must be in [1, {n_sources}], got {quorum}"
            )
        if labels is not None and len(labels) != n_sources:
            raise ValueError(
                f"{len(labels)} labels for {n_sources} sources"
            )
        if weights is not None:
            from rayfed_tpu.fl.fedavg import _check_weights

            if len(weights) != n_sources:
                raise ValueError(
                    f"{len(weights)} weights for {n_sources} sources"
                )
            self._weights = [float(w) for w in weights]
            self._total_w = _check_weights(self._weights)
        else:
            self._weights = [1.0] * n_sources
            self._total_w = float(n_sources)
        # Original arg (None vs explicit): the passthrough reduce must
        # take the same code path as packed_weighted_sum's.
        self._weights_arg = (
            None if weights is None else list(self._weights)
        )
        self._allowed = allowed
        # Output dtype of the aggregate (None = the wire dtype; f32 in
        # compressed-domain mode — integer codes make no sense as an
        # output).  Keep f32 when the result feeds a server optimizer or
        # error-feedback loop — re-quantizing the mean to an aggressive
        # wire dtype is exactly the loss no residual compensates.
        self._out_dtype = None if out_dtype is None else np.dtype(out_dtype)
        self._chunk_elems = int(chunk_elems)
        # Compressed-domain (shared-grid) mode: arriving integer codes
        # fold into a donated i32 accumulator (widening multiply-add —
        # exact, associative) and the ONE fused rescale happens at
        # finalize (fedavg.finalize_packed_quantized).  ``quant`` is the
        # round's QuantGrid; every contribution's grid fingerprint is
        # checked against it before its bytes are trusted.
        self._quant = quant
        self._int_weights: Optional[List[int]] = None
        # Delta-coded rounds: the shared reference buffer (flat f32;
        # every controller holds it bit-identically) the finalize adds
        # back after the single fused rescale, kept on the device (a
        # host buffer is uploaded here, once).  A StripeAggregator gets
        # its stripe-compacted slice.
        self._quant_ref = None
        # Subclasses (StripeAggregator) fold a block SUBSET of the grid;
        # the base class folds the full buffer and cross-checks the
        # grid's total element count + per-payload grid descriptors.
        self._quant_full = True
        if quant is not None:
            if quant.mode == "delta":
                if quant_ref is None:
                    raise ValueError(
                        "a mode='delta' grid needs quant_ref= (the "
                        "round's shared reference buffer)"
                    )
                from rayfed_tpu.fl.quantize import _flat_f32

                self._quant_ref = _flat_f32(quant_ref)
            elif quant_ref is not None:
                raise ValueError(
                    "quant_ref only applies to mode='delta' grids"
                )
            from rayfed_tpu.fl.fedavg import quant_weights

            if self._chunk_elems != int(quant.chunk_elems):
                raise ValueError(
                    f"fold grid ({self._chunk_elems} elems/block) must "
                    f"match the quantization grid "
                    f"({quant.chunk_elems}) — both ARE the canonical "
                    f"packed_block_grid chunking"
                )
            iw, itotal = quant_weights(weights, n_sources)
            quant.check_weight_headroom(itotal)
            self._int_weights = iw
            # Integer totals are exactly representable in f32 up to the
            # headroom bound, so the float bookkeeping stays exact.
            self._weights = [float(w) for w in iw]
            self._total_w = float(itotal)
        # Secure aggregation (fl.secagg): contributions arrive as
        # MASKED i32 codes — ``w_i·q_i + net pairwise mask`` — and fold
        # at UNIT weight through the unchanged integer kernel (the
        # party already folded its own weight in; weighted pairwise
        # masks could not cancel).  The float weight bookkeeping above
        # stays the TRUE example counts: the quorum cutoff's Σw reweight
        # and the finalize's zero-point term need them, and both see
        # exactly the unmasked round's numbers — which is what keeps
        # masked and unmasked rounds byte-identical.  ``mask_recovery``
        # (quorum rounds): called on the worker with the member labels
        # BEFORE finalize; returns the dropout rounds' orphaned-mask
        # correction (uint32, fl.secagg.mask_correction) or None.
        self._masked = bool(masked)
        self._mask_recovery = mask_recovery
        if self._masked and quant is None:
            raise ValueError(
                "masked aggregation requires quant= (the round's shared "
                "grid) — masks live in the integer domain"
            )
        if mask_recovery is not None and not self._masked:
            raise ValueError("mask_recovery only applies with masked=True")
        # Hierarchical aggregation (fl.hierarchy): sources are REGION
        # PARTIAL SUMS ``Σ_{p∈region} w_p·q_p`` (RegionSumTree) rather
        # than per-party codes — the weights are already folded in, so
        # each source folds at UNIT weight through the unchanged
        # integer kernel (integer adds are exact + associative, which
        # is what makes hierarchical == flat byte-identical).  The
        # ``weights`` passed here are the per-region integer TOTALS,
        # so Σw (the finalize divisor and zero-point term) is the
        # whole roster's weight — exactly the flat fold's.
        # ``presummed`` names the partial-sum wire dtype (int16/int32,
        # fl.hierarchy.partial_sum_dtype — the narrowest integer that
        # holds qabs_max·W exactly).
        self._presummed = None if presummed is None else str(presummed)
        if self._presummed is not None:
            if quant is None:
                raise ValueError(
                    "presummed aggregation requires quant= (the round's "
                    "shared grid) — partial sums live in its integer "
                    "domain"
                )
            if self._masked:
                raise ValueError(
                    "presummed and masked are mutually exclusive (a "
                    "region partial sum is already an unmaskable fold)"
                )
            if np.dtype(self._presummed).kind != "i":
                raise ValueError(
                    f"presummed= names the partial-sum integer wire "
                    f"dtype, got {self._presummed!r}"
                )
        self._n = n_sources
        self._streams = [_Stream() for _ in range(n_sources)]
        # Quorum (k-of-n) mode: the first k completed contributions may
        # be aggregated without the rest once the deadline passes (or
        # the rest provably cannot arrive).  None = classic all-of-n.
        self._quorum = None if quorum is None else int(quorum)
        self._labels = (
            [str(x) for x in labels]
            if labels is not None
            else [f"source {i}" for i in range(n_sources)]
        )
        # Sorted indices of the contributions actually aggregated; None
        # until a cutoff excludes someone (the all-of-n hot path never
        # touches this).
        self._participating: Optional[List[int]] = None
        self._deadline_at: Optional[float] = None  # monotonic cutoff time
        # Set by transport threads that need the fold rolled back (a
        # corrupt mid-fold stream under quorum); consumed by the worker,
        # the only thread allowed to touch the accumulator.
        self._needs_reset = False
        self._cond = threading.Condition()
        self._acc = None
        # True when the integer fold runs as plain numpy slice-adds
        # instead of per-block jit calls (decided in _init_acc).
        self._np_fold = False
        self._total_elems = -1
        self._nblocks = -1
        self._wire_dtype: Optional[np.dtype] = None
        self._result: Any = None
        self._error: Optional[BaseException] = None
        self._done = False
        self._worker: Optional[threading.Thread] = None
        # Timing for the overlap metric.
        self._t_first_byte = 0.0
        self._t_all_complete = 0.0
        self._t_done = 0.0
        self._busy_s = 0.0
        self.stats: Dict[str, float] = {}

    # -- source attachment ----------------------------------------------------

    def sink(self, index: int) -> _StreamSink:
        """The chunk sink for source ``index`` (hand to recv_stream)."""
        self._ensure_worker()
        return _StreamSink(self, index)

    def add_local(self, index: int, packed_tree: Any) -> None:
        """Feed the coordinator's own contribution (no wire hop)."""
        from rayfed_tpu.fl.compression import PackedTree
        from rayfed_tpu.fl.quantize import QuantizedPackedTree

        if not isinstance(packed_tree, PackedTree):
            self.fail(
                TypeError(
                    "streaming aggregation consumes PackedTree "
                    f"contributions, got {type(packed_tree).__name__} — "
                    "produce updates with fl.compress(tree, packed=True)"
                )
            )
            return
        if self._quant is not None:
            if not isinstance(packed_tree, QuantizedPackedTree):
                self.fail(
                    TypeError(
                        "compressed-domain aggregation consumes "
                        "QuantizedPackedTree contributions — quantize "
                        "onto the round grid first (fl.quantize)"
                    )
                )
                return
            from rayfed_tpu.fl.secagg import MaskedCodeTree

            if self._masked != isinstance(packed_tree, MaskedCodeTree):
                self.fail(
                    TypeError(
                        "masked fold got an unmasked contribution"
                        if self._masked else
                        "got a MaskedCodeTree but this aggregator is "
                        "not masked — construct it with masked=True "
                        "(fl.secagg) or send plain quantized codes"
                    )
                )
                return
            from rayfed_tpu.fl.hierarchy import RegionSumTree

            if (self._presummed is not None) != isinstance(
                packed_tree, RegionSumTree
            ):
                self.fail(
                    TypeError(
                        "presummed fold got a per-party contribution "
                        "(expected a RegionSumTree partial sum)"
                        if self._presummed is not None else
                        "got a RegionSumTree but this aggregator is "
                        "not presummed — construct it with presummed= "
                        "(fl.hierarchy) or send per-party codes"
                    )
                )
                return
            if packed_tree.gmeta != self._quant.meta():
                self.fail(
                    ValueError(
                        f"local contribution {index} was coded on a "
                        f"different grid (fp={packed_tree.gmeta.fp:#010x}"
                        f" vs {self._quant.fingerprint():#010x})"
                    )
                )
                return
        elif isinstance(packed_tree, QuantizedPackedTree):
            self.fail(
                TypeError(
                    "got a QuantizedPackedTree but no quant= grid — "
                    "construct the aggregator with the round's "
                    "QuantGrid to fold in the compressed domain"
                )
            )
            return
        self._attach_local(index, np.asarray(packed_tree.buf).reshape(-1),
                           tree=packed_tree)

    def _attach_local(self, index: int, arr: np.ndarray, tree=None) -> None:
        """Bind a wire-hop-free contribution (a host element array)."""
        self._ensure_worker()
        now = time.perf_counter()
        with self._cond:
            s = self._streams[index]
            s.local_tree = tree
            s.elems_array = arr
            s.dtype = arr.dtype
            s.data_start = 0
            s.data_nbytes = arr.nbytes
            s.avail_bytes = arr.nbytes
            s.complete = True
            s.t_complete = now
            if not self._t_first_byte:
                self._t_first_byte = now
            self._cond.notify_all()

    def fail(self, exc: BaseException) -> None:
        with self._cond:
            if self._error is None:
                self._error = exc
            self._cond.notify_all()

    # -- sink callbacks (transport threads) -----------------------------------

    def _on_bytes(self, index: int, view: memoryview, total: int) -> None:
        s = self._streams[index]
        # Arrival contract (both transport paths): ``view`` is the
        # frame's full payload buffer and ``total`` the CONTIGUOUS
        # bytes available from offset 0.  Single-socket streams grow
        # ``total`` as the socket drains; multi-rail stripe frames
        # (wire v4) feed the growing contiguous VERIFIED-chunk prefix,
        # so ``total`` may jump by several chunks at once and never
        # covers unverified or out-of-order bytes — either way the
        # fold below only ever consumes a true prefix of the payload.
        # ALL state writes happen under the lock — a lockless extent
        # update could race a frame abort's reset and carry a dead
        # frame's byte count onto the retry's fresh buffer.  Only the
        # worker WAKE is throttled (the lock itself is ~100ns; the
        # notify storm is what would thrash).
        with self._cond:
            if s.complete:
                return
            if s.payload is not None and s.payload.obj is not view.obj:
                # A retry frame with a fresh buffer: drop the stale
                # binding (already-applied blocks stay — a retry resends
                # the identical payload, so they remain a valid prefix).
                self._reset_frame(s)
            if s.payload is None:
                s.payload = view
                if not self._t_first_byte:
                    self._t_first_byte = time.perf_counter()
                s.avail_bytes = total
            else:
                s.avail_bytes = max(s.avail_bytes, total)
            if total - s.notified_bytes >= _NOTIFY_BYTES:
                s.notified_bytes = total
                self._cond.notify_all()

    def _on_complete(self, index: int, payload) -> None:
        now = time.perf_counter()
        with self._cond:
            s = self._streams[index]
            if s.error is not None:
                # A stream that failed earlier (corrupt mid-fold, a
                # transient death) just delivered CLEAN bytes — the
                # sender's retry or the party's revival won.  Clear the
                # failure so the stream rejoins the fold pool: leaving
                # it marked would stall the ordered fold chain at this
                # index forever while the cutoff counts it complete.
                # (Any poisoned partial folds were already queued for
                # rollback when the error was recorded.)
                logger.info(
                    "contribution from %s recovered (clean retry after "
                    "%s)", self._labels[index], s.error,
                )
                s.error = None
            # Delta frames (and mailbox replays) deliver a payload
            # object the incremental view never saw — rebind.
            s.payload = memoryview(payload)
            s.avail_bytes = len(s.payload)
            s.complete = True
            s.t_complete = now
            if not self._t_first_byte:
                self._t_first_byte = now
            self._cond.notify_all()

    def _on_error(self, index: int, err: Any) -> None:
        from rayfed_tpu.exceptions import RemoteError

        if isinstance(err, BaseException):
            exc: BaseException = err
        else:
            try:
                exc = RemoteError.from_wire(err)
            except Exception:
                exc = RuntimeError(f"stream {index} failed: {err!r}")
        if self._quorum is None:
            self.fail(exc)
            return
        # Quorum mode: one dead/failed contribution is survivable — mark
        # the stream failed and let the cutoff logic aggregate the rest.
        # Deliberately NO eager "quorum unreachable" verdict here: a
        # stream error can be transient (a corrupt frame whose sender
        # retries cleanly, a blip the monitor un-declares) and
        # _on_complete clears it — the give-up decision belongs to the
        # deadline (see _maybe_cutoff_locked), which is when stragglers
        # have provably had their chance.
        with self._cond:
            s = self._streams[index]
            if s.complete or s.error is not None:
                return
            s.error = exc
            logger.warning(
                "contribution from %s failed (%s); continuing toward "
                "quorum %d/%d", self._labels[index], exc, self._quorum,
                self._n,
            )
            self._cond.notify_all()

    @staticmethod
    def _reset_frame(s: _Stream) -> None:
        """Forget a dead frame's buffer; keep the applied-block prefix
        (a sender retry re-sends the identical payload bytes)."""
        s.payload = None
        s.avail_bytes = 0
        s.notified_bytes = 0
        s.data_start = -1
        s.data_nbytes = -1
        s.dtype = None

    def _on_frame_abort(self, index: int, corrupt: bool) -> None:
        """The in-flight frame died (connection drop) or failed
        verification.  A clean drop just resets the frame state and
        waits for the sender's retry; a CORRUPT frame whose bytes were
        already folded cannot be rolled back out of the donated
        accumulator — fail the aggregation loudly rather than let a
        retry land on top of poisoned partial sums."""
        with self._cond:
            s = self._streams[index]
            if s.complete:
                return
            if corrupt and s.applied_blocks > 0:
                if self._quorum is not None:
                    # Quorum mode can afford the rollback the donated
                    # accumulator can't: zero it, forget every applied
                    # block, mark the stream failed — the worker refolds
                    # the healthy contributions from their retained
                    # payloads (a reset also happens at any cutoff, so
                    # this adds no new machinery).
                    s.error = RuntimeError(
                        f"contribution from {self._labels[index]} failed "
                        f"verification mid-fold; excluded and refolding"
                    )
                    self._reset_frame(s)
                    # The WORKER performs the actual rollback (it is the
                    # only accumulator mutator — a reset from this
                    # transport thread could race a fold in flight).
                    self._needs_reset = True
                else:
                    self._error = RuntimeError(
                        f"contribution {index} failed verification after "
                        f"{s.applied_blocks} of its blocks were already "
                        f"aggregated — the donated accumulator cannot be "
                        f"rolled back; re-run the round"
                    )
            else:
                self._reset_frame(s)
            self._cond.notify_all()

    def _reset_fold_locked(self) -> None:
        """Zero the accumulator and forget all applied blocks (cutoff /
        quorum rollback).  The retained payloads and local arrays are
        the refold sources — pure local compute, no re-wire."""
        if self._acc is not None:
            if self._np_fold:
                self._acc = np.zeros(
                    self._nblocks * self._chunk_elems, np.int32
                )
            else:
                import jax.numpy as jnp

                self._acc = jnp.zeros(
                    self._nblocks * self._chunk_elems,
                    jnp.int32 if self._quant is not None else jnp.float32,
                )
        for s in self._streams:
            s.applied_blocks = 0

    def _maybe_cutoff_locked(self) -> None:
        """Quorum cutoff decision (worker loop, under the lock): once
        the deadline passes — or the stragglers provably cannot arrive —
        with at least ``quorum`` contributions complete, pin the
        participating set, reweight to its Σw, and refold.  The all-
        arrived case never reaches here with a subset, so quorum=n with
        no faults stays byte-identical to the classic path."""
        if self._quorum is None or self._participating is not None:
            return
        # Ready = complete AND healthy: a stream can be complete with a
        # still-standing error only transiently (a clean retry clears it
        # in _on_complete), but the cutoff must never pin a failed
        # stream into the participating set — its fold would stall the
        # chain forever.
        ready = [
            i for i, s in enumerate(self._streams)
            if s.complete and s.error is None
        ]
        if len(ready) == self._n:
            return  # everyone made it — nothing to cut
        failed = sum(1 for s in self._streams if s.error is not None)
        deadline_hit = (
            self._deadline_at is not None
            and time.monotonic() >= self._deadline_at
        )
        if len(ready) < self._quorum:
            # Quorum not met.  Give up only once the deadline has
            # passed AND even the still-pending healthy streams could
            # not fill it — failed streams get every chance to recover
            # (a clean retry clears the error) until then; without a
            # deadline the result() timeout is the bound, and its
            # PartyWaitTimeout names whoever never arrived.
            pending = self._n - len(ready) - failed
            if (
                deadline_hit
                and len(ready) + pending < self._quorum
                and self._error is None
            ):
                failed_names = [
                    self._labels[i]
                    for i, s in enumerate(self._streams)
                    if s.error is not None
                ]
                exc: BaseException = RuntimeError(
                    f"quorum {self._quorum}/{self._n} unreachable: only "
                    f"{len(ready)} contributions arrived by the round "
                    f"deadline and those from {failed_names} failed"
                )
                for i, s in enumerate(self._streams):
                    if s.error is not None:
                        exc.__cause__ = s.error
                        break
                self._error = exc
                self._cond.notify_all()
            return
        if not deadline_hit and not (
            failed and len(ready) + failed == self._n
        ):
            return
        self._participating = ready  # sorted by construction
        excluded = [
            self._labels[i] for i in range(self._n) if i not in set(ready)
        ]
        logger.warning(
            "quorum cutoff: aggregating %d/%d contributions "
            "(excluded: %s); reweighting to the arrived sum",
            len(ready), self._n, excluded,
        )
        from rayfed_tpu import telemetry

        telemetry.event(
            "quorum.cutoff",
            party=self._party,
            detail={
                "members": [self._labels[i] for i in ready],
                "excluded": excluded,
            },
        )
        if self._weights_arg is not None:
            from rayfed_tpu.fl.fedavg import _check_weights

            self._total_w = _check_weights(
                [self._weights[i] for i in ready]
            )
        else:
            self._total_w = float(len(ready))
        # Partial folds may include excluded streams' blocks (the fold
        # is per-arrival) — restart from zero over the participating set
        # in party order, which is exactly packed_weighted_sum over the
        # subset.
        self._reset_fold_locked()

    # -- result ---------------------------------------------------------------

    def result(self, timeout: Optional[float] = None,
               deadline_s: Optional[float] = None):
        """Block until every contribution streamed in; the aggregate as a
        :class:`~rayfed_tpu.fl.compression.PackedTree` in the wire dtype
        (``unpack``/``decompress`` restores the compute-dtype tree).

        ``deadline_s`` (quorum mode only): seconds from THIS call after
        which the wait stops for stragglers — once at least ``quorum``
        contributions are complete, the worker cuts the round over to
        the arrived set (reweighted to its Σw) instead of waiting out
        ``timeout``.  Cutoff granularity is the worker's wake interval
        (≤ 0.5 s past the deadline)."""
        if deadline_s is not None and self._quorum is None:
            raise ValueError("deadline_s needs quorum= at construction")
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cond:
            if deadline_s is not None and self._deadline_at is None:
                self._deadline_at = time.monotonic() + float(deadline_s)
                self._cond.notify_all()  # worker re-times its waits
            while not self._done and self._error is None:
                remaining = None
                if deadline is not None:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        from rayfed_tpu.exceptions import PartyWaitTimeout

                        self._error = PartyWaitTimeout(
                            f"streaming aggregation timed out after "
                            f"{timeout}s",
                            missing_parties=[
                                self._labels[i]
                                for i, s in enumerate(self._streams)
                                if not s.complete
                            ],
                        )
                        self._cond.notify_all()
                        break
                self._cond.wait(timeout=remaining)
            if self._error is not None:
                raise self._error
            return self._result

    @property
    def quorum_members(self) -> List[int]:
        """Sorted indices of the contributions the aggregate includes
        (all of them unless a quorum cutoff excluded stragglers).
        Meaningful once :meth:`result` returned."""
        with self._cond:
            if self._participating is not None:
                return list(self._participating)
            return list(range(self._n))

    @property
    def agg_overlap_frac(self) -> float:
        """Fraction of aggregation busy time hidden under the wire."""
        return self.stats.get("agg_overlap_frac", 0.0)

    # -- worker ---------------------------------------------------------------

    def _ensure_worker(self) -> None:
        with self._cond:
            if self._worker is None:
                self._worker = threading.Thread(
                    target=self._run,
                    name="rayfed-stream-agg",
                    daemon=True,
                )
                self._worker.start()

    def _parse_layout(self, s: _Stream) -> bool:
        """Locate the packed buffer inside the payload (needs only the
        manifest + skeleton-length prefix, i.e. the first chunk)."""
        if s.data_start >= 0:
            return True
        if s.payload is None or s.avail_bytes < 4:
            return False
        mv = s.payload
        (mlen,) = struct.unpack(">I", bytes(mv[:4]))
        if s.avail_bytes < 4 + mlen:
            return False
        manifest = json.loads(bytes(mv[4 : 4 + mlen]))
        s.manifest = manifest  # sideband consumers (StripeAggregator)
        leaves = manifest["leaves"]
        if not leaves or leaves[0]["k"] not in ("nd", "nds"):
            raise ValueError(
                "streaming aggregation expects a PackedTree payload "
                "(leaf 0 must be the packed wire buffer) — produce "
                "updates with fl.compress(tree, packed=True)"
            )
        spec = leaves[0]
        start = 4 + mlen + manifest["skel"]
        if spec["k"] == "nd":
            nbytes = spec["n"]
        else:
            nbytes = sum(e["n"] for e in spec["shards"])
        s.data_start = start
        s.data_nbytes = nbytes
        s.dtype = np.dtype(spec["dtype"])
        return True

    def _init_acc(self, s: _Stream) -> None:
        import jax.numpy as jnp

        itemsize = s.dtype.itemsize
        if s.data_nbytes % itemsize:
            raise ValueError("packed buffer not a whole element count")
        self._total_elems = s.data_nbytes // itemsize
        if self._total_elems >= 2**31:
            # Accumulator offsets ride int32 (jax's default index dtype
            # with x64 disabled) — beyond this a fold would silently
            # land at a wrapped offset.  Shard the model across several
            # packed trees before streaming at that scale.
            raise ValueError(
                f"packed buffer has {self._total_elems} elements — "
                f"streaming aggregation supports < 2**31 elements per "
                f"buffer; split the tree into multiple packed buffers"
            )
        self._wire_dtype = s.dtype
        if self._quant is not None:
            # Masked rounds widen the grid codes to i32 (the mod-2³²
            # ring the pairwise masks live in — fl.secagg); presummed
            # (hierarchy) rounds carry region partial sums at the
            # narrowest exact integer width; plain quantized rounds
            # carry the grid's own integer width.
            from rayfed_tpu.fl.secagg import MASKED_WIRE_DTYPE

            if self._masked:
                want_dt = MASKED_WIRE_DTYPE
            elif self._presummed is not None:
                want_dt = self._presummed
            else:
                want_dt = self._quant.wire_dtype
            if s.dtype != np.dtype(want_dt):
                mode_name = (
                    "masked" if self._masked
                    else "presummed" if self._presummed is not None
                    else "plain"
                )
                raise ValueError(
                    f"compressed-domain contribution carries "
                    f"{s.dtype} codes, this round folds {want_dt} "
                    f"({mode_name} mode) — "
                    f"sender and receiver disagree on the round shape"
                )
            if (
                self._quant_full
                and self._total_elems != self._quant.total_elems
            ):
                raise ValueError(
                    f"contribution has {self._total_elems} codes, the "
                    f"round grid covers {self._quant.total_elems} — "
                    f"all parties must quantize the identical packed "
                    f"layout"
                )
        # THE canonical grid — shared with the ring stripe schedule so
        # the fold blocks and the stripe blocks are the same blocks.
        from rayfed_tpu.fl.fedavg import packed_block_grid

        self._nblocks = packed_block_grid(
            self._total_elems, self._chunk_elems
        )
        # CPU integer folds skip jit: a per-block jit dispatch costs
        # ~100µs on the CPU backend — with N virtual parties each
        # folding a region's stripes (the hierarchy bench) that
        # dispatch tax alone dominated the round wall.  i32 adds are
        # exact and order-independent, so numpy slice-adds produce the
        # identical accumulator bit for bit (the keystone byte-identity
        # invariant holds by arithmetic, not by sharing the kernel).
        # The float path stays on jit unconditionally — XLA may fuse
        # multiply-add with different rounding than numpy's two-step —
        # and masked rounds keep the device accumulator their mod-2³²
        # correction kernel consumes.
        import jax

        self._np_fold = (
            self._quant is not None
            and not self._masked
            and jax.default_backend() == "cpu"
        )
        if self._np_fold:
            self._acc = np.zeros(
                self._nblocks * self._chunk_elems, np.int32
            )
        else:
            self._acc = jnp.zeros(
                self._nblocks * self._chunk_elems,
                jnp.int32 if self._quant is not None else jnp.float32,
            )

    def _avail_blocks(self, s: _Stream) -> int:
        if s.complete:
            return self._nblocks
        if s.data_start < 0 or s.dtype is None:
            return 0
        avail_elems = max(
            0, (min(s.avail_bytes, s.data_start + s.data_nbytes)
                - s.data_start) // s.dtype.itemsize
        )
        return min(self._nblocks, avail_elems // self._chunk_elems)

    def _chunk_np(self, src: tuple, block: int) -> np.ndarray:
        """The block's wire elements, zero-padded at the buffer tail.

        ``src`` is an under-the-lock snapshot of the stream's
        ``(elems_array, payload, dtype, data_start)``: a concurrent
        frame abort may null the live stream fields mid-fold, but the
        snapshot's bytes are a stable valid prefix of the logical
        payload (a sender retry resends identical bytes)."""
        elems_array, payload, dtype, data_start = src
        ce = self._chunk_elems
        first = block * ce
        count = min(ce, self._total_elems - first)
        if elems_array is not None:
            arr = elems_array[first : first + count]
        else:
            arr = np.frombuffer(
                payload,
                dtype=dtype,
                count=count,
                offset=data_start + first * dtype.itemsize,
            )
        if count < ce:
            pad = np.zeros(ce, dtype)
            pad[:count] = arr
            arr = pad
        return arr

    def _run(self) -> None:
        try:
            if self._runtime is not None:
                self._runtime.bind_thread()
            self._run_inner()
        # fedlint: disable=FED004 — transferred, not swallowed: fail(e) poisons every result waiter; this is the aggregator's dedicated worker thread, not the driver
        except BaseException as e:  # pragma: no cover - defensive
            logger.exception("streaming aggregator worker failed")
            self.fail(e)

    def _run_inner(self) -> None:
        kernel = None
        while True:
            with self._cond:
                if self._error is not None:
                    return
                if self._needs_reset:
                    self._needs_reset = False
                    self._reset_fold_locked()
                self._maybe_cutoff_locked()
                # The fold set: all streams, or the pinned quorum subset
                # after a cutoff (excluded stragglers are ignored even
                # if their bytes keep arriving).
                order = (
                    self._participating
                    if self._participating is not None
                    else list(range(self._n))
                )
                # Snapshot availability; validate layouts lazily.
                work: List[tuple] = []
                try:
                    for i in order:
                        s = self._streams[i]
                        if s.error is not None:
                            continue
                        if s.dtype is None and not self._parse_layout(s):
                            continue
                        if self._acc is None:
                            self._init_acc(s)
                        if (
                            s.data_nbytes
                            != self._total_elems * self._wire_dtype.itemsize
                            or s.dtype != self._wire_dtype
                        ):
                            raise ValueError(
                                f"contribution {i} layout mismatch: "
                                f"{s.data_nbytes}B {s.dtype} vs "
                                f"{self._total_elems} elems of "
                                f"{self._wire_dtype} — all parties must "
                                f"pack the same tree structure"
                            )
                except Exception as e:
                    self._error = e
                    self._cond.notify_all()
                    return
                if self._acc is not None:
                    # Party-order-per-block schedule: stream i may fold
                    # block b only once every EARLIER fold-set stream
                    # folded theirs — the result is then independent of
                    # arrival order (and, after a cutoff, identical to
                    # packed_weighted_sum over the participating subset).
                    # The chunk source is snapshotted HERE, under the
                    # lock (see _chunk_np).
                    prev: Optional[int] = None
                    for i in order:
                        s = self._streams[i]
                        if s.error is not None:
                            # Pre-cutoff: a failed stream stalls its
                            # successors until the cutoff excludes it
                            # (partial sums must not skip a party that
                            # the cutoff might still... never include).
                            break
                        limit = (
                            self._streams[prev].applied_blocks
                            if prev is not None else self._nblocks
                        )
                        target = min(self._avail_blocks(s), limit)
                        if target > s.applied_blocks:
                            work.append((
                                i, s.applied_blocks, target,
                                (s.elems_array, s.payload, s.dtype,
                                 s.data_start),
                            ))
                        prev = i
                all_complete = all(
                    self._streams[i].complete for i in order
                ) and (self._participating is not None
                       or not any(s.error is not None
                                  for s in self._streams))
                if not work:
                    if all_complete and self._acc is not None and all(
                        self._streams[i].applied_blocks == self._nblocks
                        for i in order
                    ):
                        break  # everything folded — finalize below
                    wait_s = 0.5
                    if (
                        self._deadline_at is not None
                        and self._participating is None
                    ):
                        wait_s = min(
                            wait_s,
                            max(0.05,
                                self._deadline_at - time.monotonic()),
                        )
                    self._cond.wait(timeout=wait_s)
                    continue
                if all_complete and not self._t_all_complete:
                    self._t_all_complete = max(
                        self._streams[i].t_complete for i in order
                    )
            # Apply outside the lock (sinks keep landing bytes meanwhile).
            if kernel is None and not self._np_fold:
                if self._quant is not None:
                    # The integer-accumulate path: widening i32
                    # multiply-add of the codes (fl.fedavg, beside the
                    # one-shot packed_quantized_sum chain it matches
                    # exactly — integer adds are order-independent).
                    from rayfed_tpu.fl.fedavg import (
                        quantized_accum_kernel,
                    )

                    kernel = quantized_accum_kernel(
                        self._chunk_elems, str(self._wire_dtype)
                    )
                else:
                    kernel = _accum_kernel(
                        self._chunk_elems, "float32", str(self._wire_dtype)
                    )
            for i, lo, hi, src in work:
                s = self._streams[i]
                if self._masked or self._presummed is not None:
                    # The party folded its own weight into the masked
                    # codes (pairwise masks only cancel at unit fold
                    # weight — fl.secagg), and a region partial sum
                    # already carries Σ w_p·q_p (fl.hierarchy) — both
                    # fold at unit weight.
                    w = np.int32(1)
                elif self._int_weights is not None:
                    w = np.int32(self._int_weights[i])
                else:
                    w = np.float32(self._weights[i])
                t0 = time.perf_counter()
                if self._np_fold:
                    ce = self._chunk_elems
                    wi = np.int32(w)
                    for b in range(lo, hi):
                        off = b * ce
                        self._acc[off:off + ce] += (
                            wi * self._chunk_np(src, b).astype(np.int32)
                        )
                else:
                    for b in range(lo, hi):
                        self._acc = kernel(
                            self._acc,
                            self._chunk_np(src, b),
                            np.int32(b * self._chunk_elems),
                            w,
                        )
                self._busy_s += time.perf_counter() - t0
                with self._cond:
                    s.applied_blocks = hi

        from rayfed_tpu import telemetry as _telemetry

        _tr = _telemetry.active()
        drain_ms = None
        if _tr is not None and not self._np_fold:
            # The device's end of the fold, counted from the last
            # block's arrival: the one sync tracing adds, armed only
            # (``agg.fold`` itself ends when the bytes have arrived).
            self._acc.block_until_ready()
            drain_ms = round(1e3 * (time.perf_counter() - (
                self._t_all_complete
                or max(self._streams[i].t_complete for i in order)
            )), 3)
        t0 = time.perf_counter()
        t0_wall = time.time()
        result = self._finalize()
        fin_s = time.perf_counter() - t0
        self._busy_s += fin_s
        self._t_done = time.perf_counter()
        if not self._t_all_complete:
            self._t_all_complete = self._t_done
        tail_s = max(0.0, self._t_done - self._t_all_complete)
        busy = max(self._busy_s, 1e-9)
        if _tr is not None:
            # The fold window (first byte → every block folded) and the
            # single finalize, as spans.  Wall anchors derive from the
            # perf-counter marks relative to now.
            now_p, now_w = time.perf_counter(), time.time()
            if self._t_first_byte:
                _tr.emit(
                    "agg.fold",
                    party=self._party,
                    t_start=now_w - (now_p - self._t_first_byte),
                    dur_s=max(0.0, self._t_all_complete
                              - self._t_first_byte),
                    detail={
                        "busy_ms": round(self._busy_s * 1e3, 3),
                        "parties": len(self._streams),
                        # What was folded and where: the wire dtype,
                        # host numpy slice-adds (the CPU backend's
                        # integer fold) or the jitted accumulate kernel,
                        # and the devices holding the accumulator.
                        "codes": str(self._wire_dtype),
                        "fold": "numpy" if self._np_fold else "jit",
                        "devices": (
                            [] if self._np_fold
                            else sorted(d.id for d in self._acc.devices())
                        ),
                        # What one fold kernel moves (the benchmark's
                        # ``fold_roofline``), and the fold's end on
                        # the device.
                        "chunk_elems": self._chunk_elems,
                        "acc": str(self._acc.dtype),
                        "drain_ms": drain_ms,
                    },
                )
            _tr.emit(
                "agg.finalize", party=self._party,
                t_start=t0_wall, dur_s=fin_s,
                detail={
                    "excluded": (
                        0 if self._participating is None
                        else self._n - len(self._participating)
                    ),
                },
            )
        self.stats = {
            "agg_busy_s": self._busy_s,
            "agg_tail_s": tail_s,
            "agg_wire_s": max(
                0.0, self._t_all_complete - self._t_first_byte
            ),
            "agg_overlap_frac": min(1.0, max(0.0, 1.0 - tail_s / busy)),
            "quorum_excluded": (
                0 if self._participating is None
                else self._n - len(self._participating)
            ),
            # Which sources were cut with a STANDING error (dead party,
            # verification failure) vs merely late: a coordinator-
            # failover re-establishment expects exactly the dead
            # coordinator here — anything else in the list is a second
            # fault worth an operator's eyes.
            "quorum_failed_sources": [
                self._labels[i]
                for i, s in enumerate(self._streams)
                if s.error is not None
            ],
        }
        with self._cond:
            self._result = result
            self._done = True
            self._cond.notify_all()

    def _finalize(self):
        """Divide + cast once, rebuild the PackedTree around the
        aggregated buffer (spec/passthrough from one template
        contribution — they are structural, identical across parties).
        Runs on the worker after every block folded; overridden by
        :class:`StripeAggregator` to emit a bare stripe buffer."""
        from rayfed_tpu.fl.compression import PackedTree, PackSpec

        members = (
            self._participating
            if self._participating is not None
            else list(range(self._n))
        )
        if self._quant is not None:
            # ONE fused rescale of the i32 code sums; every wire
            # payload's grid descriptor is verified against the round
            # grid first — wrong-grid codes must never rescale.
            from rayfed_tpu.fl.fedavg import finalize_packed_quantized

            self._verify_quant_members(members)
            if self._masked and self._mask_recovery is not None:
                # Dropout mask recovery (quorum rounds): the hook runs
                # the announce/reply round trip with the survivors and
                # returns the orphaned-mask correction — which must be
                # subtracted BEFORE the rescale (this worker is the
                # only accumulator mutator, so mid-round recovery can
                # only live here).  With no dropouts it still announces
                # the pinned member set (the survivors' receive
                # protocol is deterministic) and returns None.
                corr = self._mask_recovery(
                    [self._labels[i] for i in members]
                )
                if corr is not None:
                    from rayfed_tpu.fl.fedavg import (
                        masked_correction_kernel,
                    )

                    corr = np.asarray(corr, np.uint32).reshape(-1)
                    if corr.size != self._total_elems:
                        raise ValueError(
                            f"mask correction covers {corr.size} "
                            f"elements, round folds {self._total_elems}"
                        )
                    pad = self._nblocks * self._chunk_elems - corr.size
                    if pad:
                        corr = np.concatenate(
                            [corr, np.zeros(pad, np.uint32)]
                        )
                    self._acc = masked_correction_kernel()(
                        self._acc, corr
                    )
            out_dt = self._out_dtype or np.dtype(np.float32)
            out_buf = finalize_packed_quantized(
                self._acc, self._quant.scales, self._quant.zps,
                self._total_w, self._total_elems, self._chunk_elems,
                out_dt, ref=self._quant_ref,
            )
        else:
            from rayfed_tpu.fl.fedavg import finalize_packed_stripe

            out_dt = self._out_dtype or self._wire_dtype
            out_buf = finalize_packed_stripe(
                self._acc, self._total_w, self._total_elems, out_dt
            )
        out_buf.block_until_ready()
        template = self._template_tree()
        passthrough = template.passthrough
        if passthrough:
            # Non-float leaves get the same per-leaf averaging the
            # one-shot path applies (every payload is still retained as
            # a zero-copy view, so decoding the skeletons is cheap).
            # After a quorum cutoff only the participating trees reduce,
            # with the matching weight subset.
            from rayfed_tpu.fl.fedavg import _reduce_passthrough

            passthrough = _reduce_passthrough(
                [self._tree_of(self._streams[i]).passthrough
                 for i in members],
                None if self._weights_arg is None
                else [self._weights[i] for i in members],
                self._total_w,
            )
        spec = template.spec
        if str(out_dt) != spec.wire_dtype:
            spec = PackSpec(spec.entries, spec.treedef, np.dtype(out_dt).name)
        return PackedTree(out_buf, passthrough, spec)

    def _verify_quant_members(self, members) -> None:
        """Grid agreement check before the rescale: every member
        payload (retained as a zero-copy view — decode is cheap) must
        be a QuantizedPackedTree coded on exactly the round grid.
        Local contributions were checked at ``add_local``."""
        from rayfed_tpu.fl.hierarchy import RegionSumTree
        from rayfed_tpu.fl.quantize import QuantizedPackedTree
        from rayfed_tpu.fl.secagg import MaskedCodeTree

        want = self._quant.meta()
        for i in members:
            s = self._streams[i]
            if s.local_tree is not None:
                continue
            tree = self._tree_of(s)
            if not isinstance(tree, QuantizedPackedTree):
                raise TypeError(
                    f"contribution from {self._labels[i]} is not a "
                    f"QuantizedPackedTree — all parties must quantize "
                    f"onto the round's shared grid"
                )
            if self._masked != isinstance(tree, MaskedCodeTree):
                raise TypeError(
                    f"contribution from {self._labels[i]} is "
                    f"{'unmasked' if self._masked else 'masked'} but "
                    f"this round folds "
                    f"{'masked' if self._masked else 'plain'} codes — "
                    f"all parties must agree on secure_agg for the round"
                )
            if (self._presummed is not None) != isinstance(
                tree, RegionSumTree
            ):
                raise TypeError(
                    f"contribution from {self._labels[i]} is "
                    f"{'a per-party code tree' if self._presummed is not None else 'a RegionSumTree partial sum'}"
                    f" but this fold is "
                    f"{'presummed' if self._presummed is not None else 'per-party'}"
                    f" — hierarchy levels must agree on the round shape"
                )
            if tree.gmeta != want:
                raise ValueError(
                    f"contribution from {self._labels[i]} was coded on "
                    f"a different grid (fp={tree.gmeta.fp:#010x} vs "
                    f"{want.fp:#010x}) — aborting before the rescale; "
                    f"re-run the round on one grid"
                )

    def _tree_of(self, s: _Stream):
        from rayfed_tpu.fl.compression import PackedTree
        from rayfed_tpu.transport import wire as wire_mod

        if s.local_tree is not None:
            return s.local_tree
        tree = wire_mod.decode_payload(
            s.payload, allowed=self._allowed, zero_copy=True
        )
        if not isinstance(tree, PackedTree):
            raise TypeError(
                "streaming aggregation consumes PackedTree payloads, got "
                f"{type(tree).__name__}"
            )
        return tree

    def _template_tree(self):
        members = (
            self._participating
            if self._participating is not None
            else list(range(self._n))
        )
        for i in members:
            if self._streams[i].local_tree is not None:
                return self._streams[i].local_tree
        return self._tree_of(self._streams[members[0]])


class StripeAggregator(StreamingAggregator):
    """Fold one *stripe* of the packed chunk grid as its bytes arrive.

    The ring topology (:mod:`rayfed_tpu.fl.ring`) stripes the packed
    buffer's chunk grid across the sorted party ring; each stripe owner
    runs one of these over the compacted stripe payloads its peers send
    (leaf 0 of each payload is the stripe's chunks back to back, in
    ascending block order).  Everything else — the thread-safe sinks,
    the frame-abort semantics, and crucially the **party-order-per-
    block fold schedule** — is inherited from
    :class:`StreamingAggregator`, and the finalize is the shared
    :func:`rayfed_tpu.fl.fedavg.finalize_packed_stripe`.  Because both
    the fold chain and the divide+cast are elementwise, the stripe
    result is byte-identical to the corresponding element range of the
    whole-buffer aggregate: assembling the N stripes reproduces
    ``packed_weighted_sum`` exactly.

    ``expect_elems``: the stripe's element count, known to the owner
    from the canonical schedule — a mis-wired payload fails fast with a
    layout error instead of folding into the wrong offsets.
    ``meta_check``: called with the payload's ``rsm`` manifest string
    (its last — ``py`` — leaf) BEFORE any of that stream's blocks fold;
    the ring passes its schedule cross-check here, so two parties
    disagreeing on the chunk grid abort loudly instead of folding
    equal-sized-but-differently-composed stripes into wrong offsets.
    """

    def __init__(
        self,
        n_sources: int,
        weights: Optional[Sequence[float]] = None,
        allowed: Optional[Dict[str, Any]] = None,
        chunk_elems: int = DEFAULT_CHUNK_ELEMS,
        out_dtype: Any = None,
        expect_elems: Optional[int] = None,
        label: str = "stripe",
        meta_check: Optional[Any] = None,
        quant: Optional[Any] = None,
        quant_blocks: Optional[Sequence[int]] = None,
        quant_ref: Optional[Any] = None,
        party: Optional[str] = None,
    ) -> None:
        super().__init__(
            n_sources, weights=weights, allowed=allowed,
            chunk_elems=chunk_elems, out_dtype=out_dtype,
            party=party,
            quant=quant,
            # The stripe's compacted slice of the shared reference (the
            # base-class size check against the FULL grid is skipped
            # via _quant_full below).
            quant_ref=quant_ref,
        )
        self._expect_elems = (
            None if expect_elems is None else int(expect_elems)
        )
        self._label = label
        self._meta_check = meta_check
        # Compressed-domain stripes: the stripe's GLOBAL block indices
        # (ascending, the compaction order) select this owner's
        # scale/zero-point rows out of the round grid for its finalize.
        # Stripe payloads are bare code arrays (grid agreement is the
        # ring's rsm cross-check, not a per-payload descriptor), so the
        # base class's full-buffer checks are skipped.
        self._quant_full = False
        if quant is not None and quant_blocks is None:
            raise ValueError(
                f"{label}: compressed-domain stripes need quant_blocks "
                f"(the stripe's global block indices)"
            )
        self._quant_blocks = (
            None if quant_blocks is None
            else [int(b) for b in quant_blocks]
        )

    def _parse_layout(self, s: _Stream) -> bool:
        already = s.data_start >= 0
        if not super()._parse_layout(s):
            return False
        if self._meta_check is not None and not already and s.manifest is not None:
            # Wire payloads only (the owner's own stripe needs no
            # manifest; s.manifest is the base parse's — one decode per
            # stream); runs once, before any of its blocks fold.
            last = s.manifest["leaves"][-1]
            if last.get("k") != "py" or not isinstance(last.get("v"), str):
                raise ValueError(
                    f"{self._label}: stripe payload is missing its "
                    f"'rsm' manifest leaf"
                )
            self._meta_check(last["v"])
        return True

    def add_local(self, index: int, stripe: Any) -> None:
        """Feed the owner's own stripe (a 1-D wire-dtype host array)."""
        arr = np.asarray(stripe).reshape(-1)
        if (
            self._expect_elems is not None
            and arr.size != self._expect_elems
        ):
            self.fail(
                ValueError(
                    f"{self._label}: local stripe has {arr.size} "
                    f"elements, schedule expects {self._expect_elems}"
                )
            )
            return
        if (
            self._quant is not None
            and arr.dtype != np.dtype(self._quant.wire_dtype)
        ):
            self.fail(
                ValueError(
                    f"{self._label}: local stripe is {arr.dtype}, the "
                    f"round grid codes {self._quant.wire_dtype}"
                )
            )
            return
        self._attach_local(index, arr)

    def _init_acc(self, s: _Stream) -> None:
        super()._init_acc(s)
        if (
            self._expect_elems is not None
            and self._total_elems != self._expect_elems
        ):
            raise ValueError(
                f"{self._label}: contribution carries "
                f"{self._total_elems} elements, schedule expects "
                f"{self._expect_elems} — ring peers disagree on the "
                f"stripe layout"
            )

    def payload_value(self, index: int) -> Any:
        """Decode the full payload of source ``index`` (the stripe dict
        with its sideband fields) — retained as a zero-copy view, so
        this is cheap.  None for the owner's own (local) source."""
        from rayfed_tpu.transport import wire as wire_mod

        s = self._streams[index]
        if s.payload is None:
            return None
        return wire_mod.decode_payload(
            s.payload, allowed=self._allowed, zero_copy=True
        )

    def _finalize(self):
        """Bare stripe buffer in the output dtype (host array): the
        assembly step scatters it back onto the chunk grid."""
        if self._quant is not None:
            # The stripe's rows of the round grid: stripe block i of
            # the compacted payload IS global block quant_blocks[i], so
            # the per-row rescale is elementwise-identical to the
            # whole-buffer finalize at those element positions — the
            # keystone of ring/coordinator byte-identity, now in the
            # compressed domain.
            from rayfed_tpu.fl.fedavg import finalize_packed_quantized

            if len(self._quant_blocks) != self._nblocks:
                raise ValueError(
                    f"{self._label}: {self._nblocks} folded blocks vs "
                    f"{len(self._quant_blocks)} scheduled quant blocks"
                )
            scales, zps = self._quant.rows(self._quant_blocks)
            out_dt = self._out_dtype or np.dtype(np.float32)
            out_buf = finalize_packed_quantized(
                self._acc, scales, zps, self._total_w,
                self._total_elems, self._chunk_elems, out_dt,
                ref=self._quant_ref,
            )
        else:
            from rayfed_tpu.fl.fedavg import finalize_packed_stripe

            out_dt = self._out_dtype or self._wire_dtype
            out_buf = finalize_packed_stripe(
                self._acc, self._total_w, self._total_elems, out_dt
            )
        out_buf.block_until_ready()
        return np.asarray(out_buf)


# Seq ids one streaming_aggregate call consumes — callers pre-allocating
# ids for an off-main-thread call (fl.overlap's comms lane) draw exactly
# this many from runtime.next_seq_id() in program order.
STREAM_AGG_SEQ_IDS = 2


def streaming_aggregate(
    fed_objects: Sequence[Any],
    weights: Optional[Sequence[float]] = None,
    *,
    coordinator: Optional[str] = None,
    stream: str = "sagg",
    timeout: Optional[float] = None,
    out_dtype: Any = None,
    seq_ids: Optional[Sequence[int]] = None,
    round_tag: Optional[int] = None,
    timings: Optional[Dict[str, float]] = None,
    quant: Optional[Any] = None,
    quant_ref: Optional[Any] = None,
    quant_scope: Optional[str] = None,
    quant_downlink: bool = False,
    secagg: Optional[Any] = None,
    server_step: Optional[Any] = None,
) -> Any:
    """FedAvg round over the streaming + delta-cache pipeline.

    Drop-in for ``fl.aggregate(...)`` in coordinator topology when the
    contributions are PackedTrees: every party calls it at the same
    program point with the same arguments.  Owners push their update to
    the coordinator on a per-party **delta stream** (round-over-round
    unchanged chunks never cross the wire); the coordinator folds each
    arriving chunk into a donated on-device accumulator while later
    chunks are in flight, and broadcasts the aggregate (also on a delta
    stream).  Returns the averaged PackedTree on every party.

    ``stream`` names the delta-cache scope — keep it constant across
    rounds of the same training loop so the caches hit.

    ``seq_ids``: :data:`STREAM_AGG_SEQ_IDS` pre-allocated rendezvous ids
    ``(contrib_id, result_id)``.  Default (None) allocates them here —
    correct whenever the call runs on the thread driving the fed
    program.  A call dispatched to a background lane (the pipelined
    round engine, :mod:`rayfed_tpu.fl.overlap`) MUST pass ids drawn on
    the main thread instead: an off-thread ``next_seq_id`` would
    interleave nondeterministically with the main thread's task ids and
    desync the controllers' rendezvous streams.

    ``round_tag`` stamps every frame of the round (contributions and
    broadcast) with the round index (``wire.ROUND_TAG_KEY``).

    ``timings`` (optional dict) receives ``push_s`` (this party's
    contribution pushes ACKed, 0.0 on the coordinator — its own
    contribution never crosses the wire) and ``agg_s`` (wall time of the
    whole call).

    ``quant``: the round's shared :class:`~rayfed_tpu.fl.quantize.
    QuantGrid` — aggregate **in the compressed domain**: each party's
    contribution is quantized onto the grid before the push (already-
    quantized contributions pass through after a fingerprint check),
    frames carry the grid descriptor (``wire.QUANT_GRID_KEY``), the
    coordinator folds the integer codes into a donated i32 accumulator
    and rescales ONCE at finalize.  ``quant_ref``: the round's shared
    reference buffer (PackedTree or flat f32 buffer, bit-identical on
    every controller — the round's starting model) for ``mode="delta"``
    grids: parties code ``update − ref`` and the finalize adds ``ref``
    back.  ``out_dtype`` defaults to f32 in this mode.  ``quant_scope``
    keys the per-process error-feedback residual
    (:func:`rayfed_tpu.fl.quantize.compressor`) — None quantizes
    statelessly (no EF; parity tests).  ``quant_downlink``
    re-quantizes the broadcast onto a FRESH grid derived from the
    aggregate (carried in the payload, no negotiation needed) so the
    downlink bytes drop too; every party — coordinator included —
    returns the identical dequantized tree.

    ``server_step`` (:mod:`rayfed_tpu.fl.server_opt`): a finalize-side
    hook the COORDINATOR applies to the exact finalized aggregate
    before the result broadcast — the broadcast (and, with
    ``quant_downlink``, the re-quantized downlink, whose fresh grid is
    therefore ranged by the POST-step delta) carries the post-step
    model, so every controller returns the stepped bytes and advances
    its replicated optimizer state from them.  A step failure aborts
    the round on every controller (peers' parked broadcast is
    poisoned) — never a silent pre-step broadcast.

    Multi-host parties: only the party LEADER process runs the
    cross-party wire, so streaming aggregation works on the leader and
    raises ``NotImplementedError`` on non-leader coordinator processes
    — use :func:`rayfed_tpu.fl.aggregate` for multi-host coordinators.
    """
    from rayfed_tpu.fed_object import FedObject
    from rayfed_tpu.proxy import recv_on_runtime, send_on_runtime
    from rayfed_tpu.runtime import get_runtime

    runtime = get_runtime()
    objs = list(fed_objects)
    if not objs:
        raise ValueError("streaming_aggregate needs at least one object")
    if weights is not None and len(weights) != len(objs):
        raise ValueError(
            f"{len(weights)} weights for {len(objs)} objects"
        )
    for obj in objs:
        if not isinstance(obj, FedObject):
            raise TypeError(
                "streaming_aggregate consumes FedObjects (party-owned "
                f"contributions), got {type(obj).__name__}"
            )
    if quant_downlink and quant is None:
        raise ValueError("quant_downlink requires quant= (the grid)")
    if secagg is not None and quant is None:
        raise ValueError(
            "secagg= requires quant= — masks live in the shared-grid "
            "integer domain (fl.secagg)"
        )
    if server_step is not None and secagg is not None:
        raise ValueError(
            "server_step does not compose with masked (secure_agg) "
            "rounds yet — the recovery window has not been exercised "
            "with a post-finalize step (loud exclusion, see "
            "fl.server_opt)"
        )
    # The sender-side codec discipline (grid check + EF two-phase
    # commit), shared verbatim with ring/quorum; a no-op when quant is
    # None.  ``secagg`` (a fl.secagg.RoundMasker) swaps in the masked
    # codec: same discipline, plus the fused weight-and-mask step — the
    # coordinator then folds at unit weight and the masks cancel
    # bit-exactly (no dropout recovery here: the all-of-n path fails
    # the round on any loss, so no masks can orphan).
    from rayfed_tpu.fl import quantize as qz

    if quant is not None and out_dtype is None:
        # Integer codes make no sense as an output dtype — the
        # compressed-domain aggregate materializes in f32.
        out_dtype = np.float32
    if secagg is not None:
        from rayfed_tpu.fl.secagg import MaskedRoundCodec

        codec = MaskedRoundCodec(quant, quant_ref, quant_scope, secagg)
    else:
        codec = qz.RoundCodec(quant, quant_ref, quant_scope)
    qref = codec.ref
    q_descriptor = codec.descriptor
    _to_wire = codec.to_wire
    _quant_commit = codec.commit
    _quant_rollback = codec.rollback

    # Allocated identically on every controller — the determinism
    # contract that keys the rendezvous.
    if seq_ids is None:
        contrib_id = runtime.next_seq_id()
        result_id = runtime.next_seq_id()
    else:
        contrib_id, result_id = seq_ids
    t_call0 = time.perf_counter()
    me = runtime.party
    coord = coordinator or objs[0].get_party()
    backstop = timeout if timeout is not None else runtime.job_config.recv_backstop_s
    parties = list(runtime.cluster_config.parties)

    if me != coord:
        own_seq = 0  # per-OWNER ordinal: stable under client sampling,
        # unlike the global position (which churns with the active set
        # and would rotate delta-stream names every round).
        push_done: List[float] = []
        for obj in objs:
            if obj.get_party() == me:
                local_ref = obj.get_local_ref()
                if quant is not None:
                    # Quantize on the resolving thread (the task-pool
                    # worker that produced the update) — one fused
                    # kernel, then the uint8 codes are what the delta
                    # cache diffs and the wire ships.
                    local_ref = local_ref.then(_to_wire)
                push_ref = send_on_runtime(
                    runtime, coord, local_ref,
                    obj.get_fed_task_id(), contrib_id,
                    # Masked codes are fresh uniform noise every round:
                    # a delta stream would hash every chunk and pin a
                    # model-sized base for zero hits — send plain.
                    stream=(
                        None if secagg is not None
                        else f"{stream}/up/{me}/{own_seq}"
                    ),
                    round_tag=round_tag,
                    quant_meta=q_descriptor,
                )
                if timings is not None:
                    push_ref.add_done_callback(
                        lambda _r: push_done.append(time.perf_counter())
                    )
                own_seq += 1
        ref = recv_on_runtime(runtime, coord, result_id, result_id)
        try:
            result = ref.resolve(timeout=backstop)
        except BaseException:
            _quant_rollback()
            raise
        _quant_commit()
        if quant is not None and isinstance(
            result, qz.QuantizedPackedTree
        ):
            # Quantized downlink: decode with the grid the payload
            # itself carries — bit-identical to the coordinator's own
            # return value (same codes, same rescale, same shared ref).
            result = result.dequantize(
                np.dtype(out_dtype),
                ref=qref if result.gmeta.mode == "delta" else None,
            )
        if timings is not None:
            # The result broadcast only lands after the coordinator
            # folded every contribution, so the ACK timestamps are
            # complete by now.
            timings["push_s"] = (
                max(push_done) - t_call0 if push_done else 0.0
            )
            timings["agg_s"] = time.perf_counter() - t_call0
        return result

    agg = StreamingAggregator(
        len(objs),
        weights=weights,
        allowed=runtime.cluster_config.serializing_allowed_list,
        out_dtype=out_dtype,
        party=me,
        quant=quant,
        quant_ref=qref,
        masked=secagg is not None,
        # The fold grid IS the quantization grid (both are the
        # canonical packed_block_grid chunking).
        chunk_elems=(
            quant.chunk_elems if quant is not None else DEFAULT_CHUNK_ELEMS
        ),
    )
    pending_cancels: List[tuple] = []
    sink_entries: List[tuple] = []
    for i, obj in enumerate(objs):
        if obj.get_party() == me:
            local_ref = obj.get_local_ref()

            def _feed(ref, i=i):
                exc = ref.exception()
                if exc is not None:
                    agg.fail(exc)
                else:
                    try:
                        agg.add_local(i, _to_wire(ref.resolve()))
                    # fedlint: disable=FED004 — transferred, not swallowed: fail(e) poisons every result waiter; this callback runs on the resolving task-pool thread, not the driver
                    except BaseException as e:
                        agg.fail(e)

            local_ref.add_done_callback(_feed)
        else:
            sink_entries.append(
                (obj.get_party(), obj.get_fed_task_id(), contrib_id,
                 agg.sink(i))
            )
            pending_cancels.append((obj.get_fed_task_id(), contrib_id))
    if sink_entries:
        # One loop hop registers every contribution sink (and enrolls
        # their source parties with the health monitor's fail-fast).
        runtime.transport.recv_stream_many(sink_entries)
    others = [p for p in parties if p != me]
    try:
        result = agg.result(timeout=backstop)
        if server_step is not None:
            # The server-optimization step consumes the EXACT finalized
            # f32 aggregate (fl.server_opt); inside the try so a step
            # failure poisons the peers' parked broadcast like any
            # other coordinator-side failure.
            result = server_step(result)
    except BaseException as exc:
        _quant_rollback()
        for up, down in pending_cancels:
            runtime.transport.cancel_stream(up, down)
        # Fail-fast parity with aggregate(): the peers are parked on the
        # result broadcast — poison that key so their recv raises the
        # coordinator's error now, not after the hour-long backstop.
        poison = getattr(runtime.transport, "_send_poison", None)
        if poison is not None:
            for p in others:
                try:
                    poison(p, result_id, result_id, exc)
                except Exception:  # pragma: no cover - best effort
                    logger.exception(
                        "failed to poison streaming result for %s", p
                    )
        raise
    from rayfed_tpu.proxy import send_many_on_runtime

    _quant_commit()
    wire_result = result
    down_descriptor = None
    if quant_downlink:
        # Re-quantize the aggregate for the broadcast on a FRESH grid
        # derived from the aggregate itself (qz.quantize_downlink —
        # shared with quorum_aggregate so the two downlinks stay
        # byte-identical); the coordinator returns the DEQUANTIZED
        # codes, so every controller holds the identical bytes.
        wire_result, result, down_descriptor = qz.quantize_downlink(
            result, quant, qref, quant_scope, out_dtype=out_dtype
        )
    if others:
        send_many_on_runtime(
            runtime, others, wire_result, result_id, result_id,
            stream=f"{stream}/down", round_tag=round_tag,
            quant_meta=down_descriptor,
        )
    if timings is not None:
        timings["push_s"] = 0.0  # own contribution never hits the wire
        timings["agg_s"] = time.perf_counter() - t_call0
    return result
