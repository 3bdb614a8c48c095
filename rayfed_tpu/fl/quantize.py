"""Shared-grid integer quantization: the compressed-domain wire codec.

The PR 1-5 comms campaign made the *wire* cheap (packed-tree codec,
delta cache, striping) but the *fold* still paid full price: every
quantized chunk was dequantized to f32 before it touched the donated
accumulator, so aggregation memory traffic scaled with the f32 model.
Per THC (arXiv:2302.08545), a **shared quantization grid** makes the
sum commute with the encoding::

    sum_i w_i * x_i  ==  scale_b * (sum_i w_i * q_i  -  zp_b * W)      (*)

where every party quantizes block ``b`` of its packed update with the
SAME per-block affine grid ``x ~ scale_b * (q - zp_b)`` and
``W = sum_i w_i``.  The aggregator then folds the **integer codes**
(a widening i32 multiply-add — exact, associative) and applies ONE
fused rescale at finalize.  Bytes on the wire drop to the integer
width (uint8 = half of bf16) and the fold's HBM traffic drops with
them.

This module is the **codec half** of the compressed-domain split:

- :class:`QuantGrid` — the per-round shared grid (scale/zero-point per
  :func:`rayfed_tpu.fl.fedavg.packed_block_grid` block — the single
  canonical chunking every fold schedule already uses).  Derived
  deterministically from a reference buffer every controller holds
  identically, so "negotiation" is a pure function: the coordinator's
  grid and every party's grid are bit-identical, the
  compact descriptor rides every quantized frame's metadata
  (``wire.QUANT_GRID_KEY``), and the aggregator REJECTS any
  contribution whose grid fingerprint differs from its own.  The
  derivation reads the buffer where it lives: ONE device reduction
  (``jit_fed_quant_stats``, :func:`block_stats`) gives every block's
  min, max and float32 sum of squares, those ``nblocks x 3`` numbers
  are all that crosses to the host, and :func:`make_round_grid`
  finishes in numpy over them.  What the bit-identity rests on: min and
  max are exact on any backend; the global RMS (the range floor) is the
  exactly rounded float64 sum (``math.fsum``) of the per-block sums, so
  it depends on each block's float32 sum alone — and that sum's order
  is the compiler's, so controllers agree to the bit when they compile
  the same program for the same kind of device (one process's parties,
  a federation of like accelerators, CPUs on the same jaxlib).  The
  fingerprint check is the loud guard for anything else.  The round
  loop uses ``mode="delta"``: parties code ``update − shared model``
  on a grid ranged by the PREVIOUS round's observed aggregate delta —
  per-round updates are orders of magnitude smaller than the params,
  so the 8-bit step resolves the learning signal itself and converged
  accuracy matches the bf16 baseline (coding absolute params on a
  model-ranged grid drowns the update in the grid step; measured: it
  stalls training completely).  The first round, with no observed
  delta, runs unquantized.
- :class:`QuantizedPackedTree` — the wire form: the packed buffer's
  integer codes + the grid's scale/zero-point vectors riding alongside
  (so a delta-base re-seed, a late retry or a rejoining party always
  carries its grid with it), registered as a JAX pytree like
  :class:`~rayfed_tpu.fl.compression.PackedTree`.
- :class:`QuantCompressor` — the sender-side error-feedback state: the
  residual the grid dropped this round is added back next round (same
  EF14 scheme as :class:`~rayfed_tpu.fl.compression.ErrorFeedback`),
  which is what keeps 8-bit wire convergent with the bf16 baseline.
  Quantization is two-phase (``quantize`` → ``commit``/``rollback``) so
  a ring round that aborts and re-aggregates over the coordinator
  topology re-quantizes the SAME update with the SAME residual instead
  of double-applying it.

The **aggregator half** lives where the folding already lives:
:func:`rayfed_tpu.fl.fedavg.packed_quantized_sum` /
:func:`~rayfed_tpu.fl.fedavg.quantized_accum_kernel` /
:func:`~rayfed_tpu.fl.fedavg.finalize_packed_quantized` (the one-shot
reduce, the donated-i32 chunk kernel and the single fused rescale) and
the integer-accumulate paths of
:class:`rayfed_tpu.fl.streaming.StreamingAggregator` /
:class:`~rayfed_tpu.fl.streaming.StripeAggregator`.  Codecs know
nothing about folding; aggregators select their fold kernel from the
codec's wire form — that seam is the codec/aggregator split.

Overflow headroom (i32 widening bound vs party count): a folded code
is bounded by ``qabs_max = max(|qmin|, |qmax|)`` (255 for uint8), so
the i32 accumulator holds ``|acc| <= qabs_max * W``.  The integer path
therefore requires non-negative **integral** weights (FedAvg example
counts) with ``qabs_max * W <= 2**31 - 1`` — W up to ~8.4M at uint8,
validated loudly at aggregator construction.  W also stays exactly
representable in the f32 finalize (8.4M < 2**24 * 2 is not enough on
its own; 2**31/255 ≈ 8.42e6 < 2**24 ≈ 16.7M is).
"""

from __future__ import annotations

import functools
import json
import math
import zlib
from typing import Any, Dict, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from rayfed_tpu import telemetry
from rayfed_tpu.fl.compression import PackedTree, PackSpec

# Version of the shared-grid descriptor/semantics.  Bump when the grid
# schema (``grid_descriptor``) or the quantization transfer function
# changes — ``tool/check_wire_format.py`` fingerprints both, so drift
# without a bump fails the build like any wire drift.
QUANT_GRID_VERSION = 1

# Headroom factor for compressed-domain uplink grids: the grid range is
# the previous round's aggregate delta expanded by this — per-party
# deltas overshoot their mean (the mean averages them down), and what
# still clips rides the error-feedback residual into the next round.
# Shared by every driver loop (classic streaming, ring, quorum) so the
# grids they derive from identical reference buffers stay bit-identical.
QUANT_DELTA_EXPAND = 4.0

# Integer wire dtypes the grid supports → (qmin, qmax).
_QRANGES: Dict[str, Tuple[int, int]] = {
    "uint8": (0, 255),
    "int8": (-128, 127),
}


def _qrange(wire_dtype: str) -> Tuple[int, int]:
    try:
        return _QRANGES[wire_dtype]
    except KeyError:
        raise ValueError(
            f"unsupported quantized wire dtype {wire_dtype!r} — one of "
            f"{sorted(_QRANGES)}"
        ) from None


class QuantGrid:
    """The per-round shared quantization grid.

    ``scales``/``zps``: one f32 scale and zero-point per canonical
    packed-buffer block (:func:`~rayfed_tpu.fl.fedavg.packed_block_grid`
    over ``total_elems`` at ``chunk_elems`` granularity).  Code ``q`` of
    block ``b`` represents ``scales[b] * (q - zps[b])``.

    Every controller must hold a bit-identical grid for the round —
    :func:`make_round_grid` guarantees that when fed the identical
    reference buffer; :meth:`fingerprint` is what receivers compare.
    """

    __slots__ = ("scales", "zps", "chunk_elems", "total_elems",
                 "wire_dtype", "mode", "_fp")

    def __init__(self, scales: np.ndarray, zps: np.ndarray,
                 chunk_elems: int, total_elems: int,
                 wire_dtype: str = "uint8", mode: str = "delta") -> None:
        from rayfed_tpu.fl.fedavg import packed_block_grid

        _qrange(wire_dtype)
        if mode not in ("abs", "delta"):
            raise ValueError(
                f"grid mode must be 'abs' or 'delta', got {mode!r}"
            )
        self.mode = mode
        self.scales = np.ascontiguousarray(scales, np.float32)
        self.zps = np.ascontiguousarray(zps, np.float32)
        self.chunk_elems = int(chunk_elems)
        self.total_elems = int(total_elems)
        self.wire_dtype = str(wire_dtype)
        nb = packed_block_grid(self.total_elems, self.chunk_elems)
        if self.scales.shape != (nb,) or self.zps.shape != (nb,):
            raise ValueError(
                f"grid has {self.scales.shape}/{self.zps.shape} "
                f"scale/zero-point entries; the canonical grid over "
                f"{self.total_elems} elements at {self.chunk_elems} "
                f"elems/block has {nb} blocks"
            )
        if not np.all(self.scales > 0):
            raise ValueError("grid scales must be strictly positive")
        self._fp: Optional[int] = None

    @property
    def nblocks(self) -> int:
        return int(self.scales.shape[0])

    @property
    def qabs_max(self) -> int:
        """Bound on |code| — the i32 headroom term (see module doc)."""
        qmin, qmax = _qrange(self.wire_dtype)
        return max(abs(qmin), abs(qmax))

    def fingerprint(self) -> int:
        """CRC32 over the grid's exact bytes + geometry — what frame
        metadata carries and receivers compare.  Bit-identical grids
        (the only kind :func:`make_round_grid` produces from identical
        references) fingerprint identically."""
        if self._fp is None:
            head = json.dumps(
                [QUANT_GRID_VERSION, self.chunk_elems, self.total_elems,
                 self.wire_dtype, self.mode],
                separators=(",", ":"),
            ).encode()
            fp = zlib.crc32(head)
            fp = zlib.crc32(self.scales.tobytes(), fp)
            fp = zlib.crc32(self.zps.tobytes(), fp)
            self._fp = fp
        return self._fp

    def meta(self) -> "QuantMeta":
        """The static descriptor stamped into quantized wire forms."""
        return QuantMeta(
            QUANT_GRID_VERSION, self.chunk_elems, self.total_elems,
            self.wire_dtype, self.mode, self.fingerprint(),
        )

    def rows(self, blocks: Sequence[int]) -> Tuple[np.ndarray, np.ndarray]:
        """(scales, zps) for a block subset — a ring stripe owner's
        rows, in the stripe's ascending-block compaction order."""
        idx = np.asarray(list(blocks), np.int64)
        return self.scales[idx], self.zps[idx]

    def check_weight_headroom(self, total_weight: int) -> None:
        """Loud i32 overflow guard: ``qabs_max * W`` must fit int32."""
        bound = self.qabs_max * int(total_weight)
        if bound > 2**31 - 1:
            raise ValueError(
                f"integer-fold overflow: qabs_max({self.wire_dtype})="
                f"{self.qabs_max} x total weight {total_weight} = "
                f"{bound} exceeds the i32 accumulator bound {2**31 - 1} "
                f"— the widening add holds only for total weight <= "
                f"{(2**31 - 1) // self.qabs_max}; rescale the example "
                f"counts or aggregate hierarchically"
            )

    def __eq__(self, other: Any) -> bool:
        return (
            isinstance(other, QuantGrid)
            and self.meta() == other.meta()
        )

    def __repr__(self) -> str:  # pragma: no cover
        return (
            f"QuantGrid({self.nblocks} blocks x {self.chunk_elems} "
            f"{self.wire_dtype} elems, {self.total_elems} total, "
            f"fp={self.fingerprint():#010x})"
        )


class QuantMeta(NamedTuple):
    """Hashable static descriptor of a grid (pytree aux / wire check).

    ``mode``: ``"delta"`` — the codes represent ``x - ref`` against the
    round's shared reference buffer (the starting model), the form the
    round loop uses (per-round updates are orders of magnitude smaller
    than the params, so the delta grid is correspondingly finer);
    ``"abs"`` — the codes represent the values themselves (no
    reference needed to decode; the downlink of a model whose receiver
    holds nothing yet).
    """

    version: int
    chunk_elems: int
    total_elems: int
    wire_dtype: str
    mode: str
    fp: int


def grid_descriptor(grid: QuantGrid) -> Dict[str, Any]:
    """The compact per-frame grid descriptor — single producer of the
    schema ``tool/check_wire_format.py`` fingerprints.  Rides the
    ordinary frame-metadata dict under ``wire.QUANT_GRID_KEY`` (JSON-
    encoded): receivers attribute a quantized frame to its round's grid
    without decoding the payload, and a mismatched fingerprint names
    both grids instead of folding wrong-grid codes.
    """
    return {
        "v": QUANT_GRID_VERSION,
        "fp": int(grid.fingerprint()),
        "nb": int(grid.nblocks),
        "ce": int(grid.chunk_elems),
        "el": int(grid.total_elems),
        "dt": str(grid.wire_dtype),
        "md": str(grid.mode),
    }


def check_descriptor(descriptor: Any, grid: QuantGrid) -> None:
    """Validate a received grid descriptor (JSON str or dict) against
    the locally derived grid; raises naming both on any mismatch."""
    gd = (
        json.loads(descriptor) if isinstance(descriptor, (str, bytes))
        else dict(descriptor)
    )
    if gd.get("v", 0) > QUANT_GRID_VERSION:
        raise ValueError(
            f"quantized frame uses grid descriptor v{gd.get('v')}; this "
            f"party understands up to v{QUANT_GRID_VERSION}"
        )
    want = grid_descriptor(grid)
    for key in ("fp", "nb", "ce", "el", "dt", "md"):
        if gd.get(key) != want[key]:
            raise ValueError(
                f"quantization grid mismatch: frame carries "
                f"{key}={gd.get(key)!r}, this round's grid has "
                f"{want[key]!r} — sender and receiver disagree on the "
                f"round's shared grid"
            )


class BlockStats(NamedTuple):
    """Per-block range statistics of one flat buffer, held where the
    buffer lives: all :func:`make_round_grid` needs of it.

    ``stats`` is an ``(nblocks, 3)`` float32 array of ``[min, max, sum
    of squares]`` per canonical block; it stays a device array until a
    grid is derived from it, and those ``nblocks x 12`` bytes are then
    all that crosses to the host.
    """

    stats: Any
    chunk_elems: int
    total_elems: int


@functools.lru_cache(maxsize=None)
def _stats_kernel(chunk_elems: int, total_elems: int, with_ref: bool):
    """ONE reduction over the whole packed buffer: per canonical block
    the min, the max and the sum of squares of ``buf [- ref]`` in
    float32.  The short tail block is reduced at its own length, which
    is what padding it by its last value gave (a zero pad would drag
    the range toward 0 for tail blocks that never contain 0).  A loop
    over the blocks, each one fused slice-subtract-reduce: neither the
    difference nor a padded copy is materialized (as one reshaped
    reduction the TPU compiler keeps both, 260 MB at 31 M elements)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    whole, tail = divmod(total_elems, chunk_elems)

    @jax.jit
    def fed_quant_stats(buf, ref):
        buf = buf.reshape(-1)

        def block(start, size):
            value = lax.dynamic_slice(buf, (start,), (size,))
            value = value.astype(jnp.float32)
            if with_ref:
                value = value - lax.dynamic_slice(ref, (start,), (size,))
            return jnp.stack(
                [value.min(), value.max(), (value * value).sum()]
            )

        rows = []
        if whole:
            rows.append(lax.map(
                lambda i: block(i * chunk_elems, chunk_elems),
                jnp.arange(whole),
            ))
        if tail:
            rows.append(block(whole * chunk_elems, tail)[None])
        return jnp.concatenate(rows)

    return fed_quant_stats


def _flat_f32(ref: Any) -> Any:
    """A reference buffer as a flat float32 device array: a device
    array stays where it is, a host array is uploaded (once, here)."""
    import jax.numpy as jnp

    if isinstance(ref, PackedTree):
        ref = ref.buf
    return jnp.asarray(ref, jnp.float32).reshape(-1)


def block_stats(
    buf: Any, ref: Optional[Any] = None, chunk_elems: Optional[int] = None,
) -> BlockStats:
    """Dispatch the grid-statistics kernel over ``buf [- ref]`` where
    the buffer lives (a numpy buffer is put on the default device) and
    return its result WITHOUT fetching it: a round loop takes the
    statistics of the broadcast it just decoded while the chip is idle,
    and :func:`make_round_grid` reads them a round later.  The
    difference itself is never materialized.
    """
    import jax.numpy as jnp

    if isinstance(buf, PackedTree):
        buf = buf.buf
    total = int(getattr(buf, "size", 0))
    if total == 0:
        raise ValueError(
            "cannot derive a quantization grid from an empty buffer"
        )
    if chunk_elems is None:
        from rayfed_tpu.fl.streaming import DEFAULT_CHUNK_ELEMS

        chunk_elems = DEFAULT_CHUNK_ELEMS
    ce = int(chunk_elems)
    with_ref = ref is not None
    if with_ref:
        ref = _flat_f32(ref)
        if int(ref.size) != total:
            raise ValueError(
                f"reference buffer has {int(ref.size)} elements, the "
                f"buffer has {total}"
            )
    else:
        ref = jnp.zeros(0, jnp.float32)  # unused placeholder arg
    return BlockStats(
        _stats_kernel(ce, total, with_ref)(buf, ref), ce, total
    )


def make_round_grid(
    reference: Any,
    chunk_elems: Optional[int] = None,
    wire_dtype: str = "uint8",
    expand: float = 1.25,
    min_scale: float = 1e-12,
    mode: str = "delta",
    floor_frac: float = 0.05,
) -> QuantGrid:
    """Derive a shared grid from a reference range buffer.

    ``reference``: a buffer every controller holds **bit-identically**
    whose per-block value range predicts the values to be coded (a
    device array, a numpy array, a :class:`PackedTree`, or the
    :class:`BlockStats` :func:`block_stats` already took of one).  For
    the round loop's ``mode="delta"`` uplink that is the PREVIOUS
    round's aggregate delta (``agg_r − agg_{r-1}``): per-party deltas
    live at the same scale, so the grid step lands orders of magnitude
    below the params and the codes carry the *signal*, not the
    ambient parameter range (the first round, with no observed delta
    yet, runs unquantized — the driver's bootstrap).  For ``mode=
    "abs"`` it is the values themselves (e.g. the aggregate the
    coordinator is about to broadcast).  The derivation is one device
    reduction over the shared buffer (``jit_fed_quant_stats``) and a
    numpy finish over its ``nblocks x 3`` results, so every controller
    computes the identical grid with no extra wire hop — that IS the
    negotiation, pinned by the fingerprint check on every quantized
    frame (see the module docstring for what the identity rests on).

    Per block: the value range is the block's [min, max] expanded by
    ``expand`` around its midpoint (values drift past the reference
    range; out-of-range values clip and the clipped mass rides the
    error-feedback residual into the next round), floored at
    ``floor_frac`` of the buffer's global RMS (a near-constant block's
    range says nothing about where its values will move — a
    dispersion-proportional floor keeps it from degenerating into a
    clip-everything trap), then mapped affinely onto the integer
    range.  ``min_scale`` floors the fully-degenerate all-zero case.

    Flight recorder: ``fl.quant.grid`` (the kernel where the reference
    is a buffer, the fetch of the statistics, the numpy finish;
    ``nbytes`` is what crossed to the host, ``nblocks x 12``;
    ``detail.side`` is ``up``, or ``down`` under a recode;
    ``detail.fp`` is the grid's fingerprint, the one receivers check).
    """
    with telemetry.span("fl.quant.grid") as sp:
        if not isinstance(reference, BlockStats):
            reference = block_stats(reference, chunk_elems=chunk_elems)
        ce, total = reference.chunk_elems, reference.total_elems
        if chunk_elems is not None and int(chunk_elems) != ce:
            raise ValueError(
                f"block statistics were taken at {ce} elems/block, the "
                f"grid is asked for at {int(chunk_elems)}"
            )
        qmin, qmax = _qrange(wire_dtype)
        stats = np.asarray(reference.stats)
        if sp is not None:
            sp.nbytes = stats.nbytes
        lo, hi = stats[:, 0], stats[:, 1]
        # Global RMS from the per-block float32 sums, combined here in
        # float64: fsum is exactly rounded, so the result depends on
        # the blocks' sums alone, not on how they are grouped.
        rms = math.sqrt(math.fsum(stats[:, 2].tolist()) / total)
        mid = 0.5 * (hi + lo)
        half = np.maximum(
            0.5 * (hi - lo) * np.float32(expand),
            np.float32(float(floor_frac) * rms),
        )
        lo = mid - half
        hi = mid + half
        scales = np.maximum(
            (hi - lo) / np.float32(qmax - qmin), np.float32(min_scale)
        ).astype(np.float32)
        zps = (qmin - lo / scales).astype(np.float32)
        grid = QuantGrid(scales, zps, ce, total, wire_dtype, mode)
        if sp is not None:
            # The coordinator's downlink recode derives its own grid.
            sp.detail = {
                "side": "down" if sp.parent == "fl.quant.recode" else "up",
                "fp": grid.fingerprint(),
            }
        return grid


class QuantizedPackedTree(PackedTree):
    """Integer-coded wire form of a :class:`PackedTree`.

    ``buf`` holds the integer codes (``gmeta.wire_dtype``); ``scales``
    and ``zps`` are the grid's per-block vectors riding alongside (tiny
    — one f32 pair per 4 MB block — and they make every payload
    self-describing: a delta-base re-seed or a rejoining party always
    carries the grid it was coded with).  ``gmeta`` is the static
    :class:`QuantMeta` descriptor; the fold layer compares its ``fp``
    against the round grid before trusting any codes.

    Registered as a JAX pytree with children ``(buf, scales, zps,
    *passthrough)`` — leaf 0 stays the packed wire buffer, so the
    transport codec and the streaming aggregator's layout parse see
    exactly the shape they already handle.
    """

    __slots__ = ("scales", "zps", "gmeta")

    def __init__(self, buf: Any, scales: Any, zps: Any,
                 passthrough: Tuple, spec: PackSpec,
                 gmeta: QuantMeta) -> None:
        super().__init__(buf, passthrough, spec)
        self.scales = scales
        self.zps = zps
        self.gmeta = gmeta

    @property
    def nbytes(self) -> int:
        total = super().nbytes
        for extra in (self.scales, self.zps):
            total += getattr(extra, "nbytes", 0)
        return total

    def grid(self) -> QuantGrid:
        """Reconstruct the grid this tree was coded with (receiver
        side: the broadcast's grid needs no prior negotiation)."""
        g = QuantGrid(
            np.asarray(self.scales), np.asarray(self.zps),
            self.gmeta.chunk_elems, self.gmeta.total_elems,
            self.gmeta.wire_dtype, self.gmeta.mode,
        )
        if g.fingerprint() != self.gmeta.fp:
            raise ValueError(
                f"quantized payload is internally inconsistent: carried "
                f"grid fingerprints {g.fingerprint():#010x}, descriptor "
                f"says {self.gmeta.fp:#010x}"
            )
        return g

    def dequantize(self, out_dtype: Any = np.float32,
                   ref: Optional[Any] = None) -> PackedTree:
        """ONE fused rescale (+ reference add, for ``mode="delta"``
        codes) of the whole buffer back to ``out_dtype`` — the decode
        half of the codec (flight recorder: ``fl.quant.decode``, the
        dispatch)."""
        with telemetry.span("fl.quant.decode"):
            grid = self.grid()
            ref = _check_ref(grid, ref)
            out_name = np.dtype(out_dtype).name
            if ref is None:
                import jax.numpy as jnp

                ref = jnp.zeros(0, jnp.float32)
            buf = _dequantize_kernel(
                self.gmeta.chunk_elems, self.gmeta.total_elems,
                self.gmeta.wire_dtype, out_name, grid.mode == "delta",
            )(self.buf, ref, np.asarray(self.scales), np.asarray(self.zps))
            spec = PackSpec(self.spec.entries, self.spec.treedef, out_name)
            return PackedTree(buf, self.passthrough, spec)

    def unpack(self, dtype: Any = None) -> Any:
        """Dequantize + unpack.  ``dtype=None`` decodes to f32 (integer
        codes are meaningless as float leaves).  Delta-coded trees need
        :meth:`dequantize` with the shared reference buffer first —
        calling this without it raises."""
        out = np.float32 if dtype is None else dtype
        return self.dequantize(out).unpack(out)

    def __reduce__(self):
        return (
            QuantizedPackedTree,
            (self.buf, self.scales, self.zps, self.passthrough,
             self.spec, self.gmeta),
        )

    def __repr__(self) -> str:  # pragma: no cover
        return (
            f"QuantizedPackedTree({self.gmeta.total_elems} "
            f"{self.gmeta.wire_dtype} codes, {self.gmeta.chunk_elems} "
            f"elems/block, fp={self.gmeta.fp:#010x}, "
            f"{len(self.passthrough)} passthrough)"
        )


import jax  # noqa: E402  (after numpy-only grid machinery)

jax.tree_util.register_pytree_node(
    QuantizedPackedTree,
    lambda qt: (
        (qt.buf, qt.scales, qt.zps, *qt.passthrough),
        (qt.spec, qt.gmeta),
    ),
    lambda aux, ch: QuantizedPackedTree(
        ch[0], ch[1], ch[2], tuple(ch[3:]), aux[0], aux[1]
    ),
)


@functools.lru_cache(maxsize=None)
def _quantize_kernel(chunk_elems: int, total_elems: int, wire_name: str,
                     with_ref: bool):
    """ONE fused (subtract-reference +) quantize + residual step over
    the whole packed buffer: add the carried residual, code onto the
    grid, dequantize in-kernel to carry the new residual.  Same EF14
    structure as ``compression._ef_kernel``, on the shared grid."""
    import jax
    import jax.numpy as jnp

    qmin, qmax = _qrange(wire_name)
    from rayfed_tpu.fl.fedavg import packed_block_grid

    nb = packed_block_grid(total_elems, chunk_elems)
    pad = nb * chunk_elems - total_elems

    @jax.jit
    def fed_quant_encode(buf, ref, scales, zps, resid):
        value = buf.astype(jnp.float32)
        if with_ref:
            value = value - ref
        corrected = value + resid
        a = jnp.pad(corrected, (0, pad)).reshape(nb, chunk_elems)
        q = jnp.clip(
            jnp.round(a / scales[:, None] + zps[:, None]), qmin, qmax
        )
        deq = scales[:, None] * (q - zps[:, None])
        qbuf = q.astype(jnp.dtype(wire_name)).reshape(-1)[:total_elems]
        new_resid = corrected - deq.reshape(-1)[:total_elems]
        return qbuf, new_resid

    return fed_quant_encode


@functools.lru_cache(maxsize=None)
def _dequantize_kernel(chunk_elems: int, total_elems: int,
                       wire_name: str, out_name: str, with_ref: bool):
    import jax
    import jax.numpy as jnp

    from rayfed_tpu.fl.fedavg import packed_block_grid

    nb = packed_block_grid(total_elems, chunk_elems)
    pad = nb * chunk_elems - total_elems

    @jax.jit
    def fed_quant_decode(qbuf, ref, scales, zps):
        a = jnp.pad(qbuf.astype(jnp.float32), (0, pad)).reshape(
            nb, chunk_elems
        )
        x = scales[:, None] * (a - zps[:, None])
        x = x.reshape(-1)[:total_elems]
        if with_ref:
            x = ref + x
        return x.astype(jnp.dtype(out_name))

    return fed_quant_decode


def _check_ref(grid: QuantGrid, ref: Optional[Any]):
    """Validate + normalize the shared reference buffer against the
    grid's mode (delta codes are meaningless without it, abs codes
    must not get one)."""
    if grid.mode == "delta":
        if ref is None:
            raise ValueError(
                "grid mode 'delta' codes x - ref: pass ref= (the "
                "round's shared reference buffer, e.g. the starting "
                "model's packed f32 buffer)"
            )
        if isinstance(ref, PackedTree):
            ref = ref.buf
        if int(getattr(ref, "size", 0)) != grid.total_elems:
            raise ValueError(
                f"reference buffer has {getattr(ref, 'size', 0)} "
                f"elements, grid covers {grid.total_elems}"
            )
        return ref
    if ref is not None:
        raise ValueError(
            "grid mode 'abs' codes the values themselves — ref= does "
            "not apply"
        )
    return None


def _quantize_with_resid(
    packed: PackedTree, grid: QuantGrid, resid: Optional[Any],
    ref: Optional[Any] = None,
) -> Tuple[QuantizedPackedTree, Any]:
    if isinstance(packed, QuantizedPackedTree):
        raise TypeError("tree is already quantized")
    if not isinstance(packed, PackedTree):
        raise TypeError(
            f"quantize_packed consumes PackedTree contributions, got "
            f"{type(packed).__name__} — pack with fl.compress(tree, "
            f"packed=True) first"
        )
    buf = packed.buf
    n = int(getattr(buf, "size", 0))
    if n != grid.total_elems:
        raise ValueError(
            f"packed buffer has {n} elements, grid covers "
            f"{grid.total_elems} — the grid must be derived on the same "
            f"packed layout the parties push"
        )
    ref = _check_ref(grid, ref)
    import jax.numpy as jnp

    if resid is None:
        resid = jnp.zeros(grid.total_elems, jnp.float32)
    if ref is None:
        ref = jnp.zeros(0, jnp.float32)  # unused placeholder arg
    # The encode kernel and the d2h of its codes (the wire ships host
    # bytes): one span, the host's time in both.
    with telemetry.span("fl.quant.encode") as sp:
        qbuf, new_resid = _quantize_kernel(
            grid.chunk_elems, grid.total_elems, grid.wire_dtype,
            grid.mode == "delta",
        )(buf, ref, grid.scales, grid.zps, resid)
        codes = np.asarray(qbuf)
        if sp is not None:
            sp.nbytes = codes.nbytes
    spec = PackSpec(
        packed.spec.entries, packed.spec.treedef, grid.wire_dtype
    )
    qt = QuantizedPackedTree(
        codes, grid.scales, grid.zps, packed.passthrough,
        spec, grid.meta(),
    )
    return qt, new_resid


def quantize_packed(
    packed: PackedTree, grid: QuantGrid, ref: Optional[Any] = None
) -> QuantizedPackedTree:
    """Stateless (no error feedback) grid quantization of a PackedTree.

    ``ref``: the shared reference buffer (``mode="delta"`` grids code
    ``x - ref``)."""
    qt, _ = _quantize_with_resid(packed, grid, None, ref)
    return qt


def dequantize_packed(
    qtree: QuantizedPackedTree, out_dtype: Any = np.float32,
    ref: Optional[Any] = None,
) -> PackedTree:
    """Decode a quantized tree back to a float PackedTree (one fused
    rescale; ``ref`` required for delta-coded trees)."""
    if not isinstance(qtree, QuantizedPackedTree):
        raise TypeError(
            f"dequantize_packed consumes QuantizedPackedTree, got "
            f"{type(qtree).__name__}"
        )
    return qtree.dequantize(out_dtype, ref)


class QuantCompressor:
    """Per-sender error-feedback state for the grid codec.

    Two-phase on purpose: :meth:`quantize` computes the coded tree and
    the *pending* residual; :meth:`commit` promotes it once the round
    that shipped the codes succeeded; :meth:`rollback` discards it.  A
    ring round that aborts after quantizing re-aggregates the SAME
    update over the coordinator fallback — with one-phase state the
    residual would be applied twice for one round of wire.

    Keep one instance per outgoing stream (see :func:`compressor`);
    :meth:`reset` it when the tree structure changes.
    """

    def __init__(self) -> None:
        self._resid: Optional[Any] = None
        self._pending: Optional[Any] = None

    @property
    def residual(self) -> Any:
        """The committed f32 residual (None before the first commit)."""
        return self._resid

    def quantize(self, packed: PackedTree, grid: QuantGrid,
                 ref: Optional[Any] = None) -> QuantizedPackedTree:
        if (
            self._resid is not None
            and int(self._resid.shape[0]) != grid.total_elems
        ):
            raise ValueError(
                f"tree structure changed under quantized error feedback "
                f"({self._resid.shape[0]} residual elements vs grid over "
                f"{grid.total_elems}) — call reset() when switching "
                f"models"
            )
        qt, self._pending = _quantize_with_resid(
            packed, grid, self._resid, ref
        )
        return qt

    def commit(self) -> None:
        if self._pending is not None:
            self._resid = self._pending
            self._pending = None

    def rollback(self) -> None:
        self._pending = None

    def reset(self) -> None:
        self._resid = None
        self._pending = None


class RoundCodec:
    """ONE round's sender-side codec discipline, shared by every
    aggregation topology (streaming / ring / quorum).

    Bundles the pieces that must stay in lockstep — the grid, the
    normalized shared reference buffer, the per-frame descriptor, the
    pre-quantized-fingerprint check, and the error-feedback two-phase
    commit/rollback — so the ring-abort → coordinator-fallback
    residual guarantee cannot silently diverge between topologies.
    With ``grid=None`` every method is the identity/no-op (the
    unquantized path needs no branches at call sites).
    """

    __slots__ = ("grid", "ref", "descriptor", "_comp")

    def __init__(self, grid: Optional[QuantGrid],
                 ref: Optional[Any] = None,
                 scope: Optional[str] = None) -> None:
        self.grid = grid
        # Resolved HERE, on the constructing (driver) thread: to_wire
        # may run on whichever thread resolves the update, and all three
        # phases must reach the same party's error-feedback state.
        self._comp = (
            compressor(scope)
            if grid is not None and scope is not None else None
        )
        self.ref: Optional[Any] = None
        self.descriptor: Optional[Dict[str, Any]] = None
        if grid is not None:
            self.descriptor = grid_descriptor(grid)
            if ref is not None:
                self.ref = _flat_f32(ref)

    def to_wire(self, value: Any) -> Any:
        """This party's contribution in wire form: quantized onto the
        round grid (a pre-quantized value passes through after a
        fingerprint check; with a scope, the error-feedback residual
        rides along — committed only after the round lands)."""
        if self.grid is None:
            return value
        if isinstance(value, QuantizedPackedTree):
            if value.gmeta != self.grid.meta():
                raise ValueError(
                    f"pre-quantized contribution was coded on a "
                    f"different grid (fp={value.gmeta.fp:#010x} vs "
                    f"{self.grid.fingerprint():#010x})"
                )
            return value
        if not isinstance(value, PackedTree):
            raise TypeError(
                "compressed-domain aggregation consumes PackedTree "
                f"contributions, got {type(value).__name__}"
            )
        if self._comp is not None:
            return self._comp.quantize(value, self.grid, ref=self.ref)
        return quantize_packed(value, self.grid, ref=self.ref)

    def commit(self) -> None:
        if self._comp is not None:
            self._comp.commit()

    def rollback(self) -> None:
        if self._comp is not None:
            self._comp.rollback()


def quantize_downlink(
    result: Any,
    grid: QuantGrid,
    ref: Optional[Any],
    scope: Optional[str],
    out_dtype: Any = np.float32,
) -> Tuple[QuantizedPackedTree, Any, Dict[str, Any]]:
    """Re-quantize a round aggregate for the result broadcast.

    The coordinator is the only sender, so the downlink grid can follow
    the exact data (FRESH grid from the aggregate itself, tiny error)
    and it rides the payload — receivers and rejoiners need no
    negotiation.  Delta rounds code ``aggregate − shared ref``, the form
    whose range the 8-bit step actually resolves.  Returns ``(wire
    form, dequantized aggregate, grid descriptor)`` — the coordinator
    returns the DEQUANTIZED codes so every controller holds the
    identical bytes.  ONE producer shared by ``streaming_aggregate``,
    ``quorum_aggregate`` and the hierarchy root: the quantized-quorum,
    quantized-streaming and hierarchical downlinks are byte-identical
    by construction, not by parallel maintenance.  Under a server
    optimizer (fl.server_opt) the caller steps BEFORE calling this, so
    ``result`` is the post-step model and the fresh grid here is
    automatically ranged by the post-step delta — no new metadata key,
    no schema change.  ``scope`` keys the downlink's own
    error-feedback residual (``{scope}/down``); None quantizes
    statelessly.  The aggregate and ``ref`` stay where they live (a
    host ``ref`` is uploaded once): the statistics, encode and decode
    kernels all read them there, and what crosses to the host is the
    grid's statistics and the codes the wire ships.  Flight recorder:
    ``fl.quant.recode`` (``nbytes``: the codes' bytes), the parent of
    its grid, encode and decode spans.
    """
    with telemetry.span("fl.quant.recode") as sp:
        if ref is not None:
            ref = _flat_f32(ref)
        down_grid = make_round_grid(
            block_stats(result, ref, chunk_elems=grid.chunk_elems),
            wire_dtype=grid.wire_dtype,
            mode="abs" if ref is None else "delta",
        )
        dcomp = compressor(f"{scope}/down") if scope is not None else None
        wire_result = (
            dcomp.quantize(result, down_grid, ref=ref)
            if dcomp is not None
            else quantize_packed(result, down_grid, ref=ref)
        )
        if sp is not None:
            sp.nbytes = wire_result.buf.nbytes
        decoded = wire_result.dequantize(np.dtype(out_dtype), ref=ref)
        if dcomp is not None:
            dcomp.commit()
        return wire_result, decoded, grid_descriptor(down_grid)


# Compressor registry, keyed by stream scope (one EF state per outgoing
# quantized stream, like the delta caches' stream keying).  The state
# is per SENDER: it lives on the calling party's Runtime, so several
# parties in one process (rayfed_tpu.inprocess) never fold one
# another's residuals; code running with no runtime (unit tests of the
# codec) shares this module-level registry.
_COMPRESSORS: Dict[str, QuantCompressor] = {}


def _registry() -> Dict[str, QuantCompressor]:
    from rayfed_tpu.runtime import get_runtime_or_none

    runtime = get_runtime_or_none()
    return _COMPRESSORS if runtime is None else runtime.quant_compressors


def compressor(scope: str) -> QuantCompressor:
    """The calling party's :class:`QuantCompressor` for ``scope``
    (created on first use).  Scope by stream name, e.g. ``"fedavg"`` for
    the round loop's uplink and ``"fedavg/down"`` for the coordinator's
    broadcast."""
    return _registry().setdefault(scope, QuantCompressor())


def reset_compressors() -> None:
    """Drop every registered compressor's state (tests / model swap)."""
    _registry().clear()
