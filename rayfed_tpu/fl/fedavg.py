"""FedAvg: cross-party weighted parameter averaging.

Multi-controller semantics (every party runs the same line): each party
contributes its local update as a ``FedObject``; :func:`aggregate` fetches
all contributions via ``fed.get`` — owners *push* to every peer per the
broadcast-on-get semantics (reference ``api.py:385-400``) — and averages
locally.  The tree arithmetic is jit-compiled, so with params sharded over
a party-local mesh the average runs as one fused XLA op per leaf on
device, and the cross-party hop is the only DCN traffic.
"""

from __future__ import annotations

import functools
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _check_weights(weights: Sequence[float]) -> float:
    """Validated total of a weight vector.

    An empty or all-zero (or non-finite) weight vector would silently
    divide the aggregate by 0 — surface it as a ValueError naming the
    problem instead of propagating inf/NaN params into the round."""
    if len(weights) == 0:
        raise ValueError("weights must be non-empty")
    total = float(sum(float(w) for w in weights))
    if total == 0.0:
        raise ValueError(
            "weights sum to zero (e.g. every party reported 0 examples) "
            "— the weighted average is undefined; drop the round or pass "
            "weights=None for a plain mean"
        )
    if not np.isfinite(total):
        raise ValueError(f"weights sum to a non-finite value ({total})")
    return total


def _mean_leaf(*leaves):
    """Mean of one leaf position: low-precision floats (e.g. bf16 from
    fl.compression) accumulate in f32 and cast back; everything else —
    including int leaves — keeps numpy's promoting arithmetic (an int
    mean stays the float it always was, never a truncated int)."""
    dt = leaves[0].dtype
    if jnp.issubdtype(dt, jnp.floating):
        acc = leaves[0].astype(jnp.float32)
        for leaf in leaves[1:]:
            acc = acc + leaf.astype(jnp.float32)
        return (acc / len(leaves)).astype(dt)
    return sum(leaves[1:], start=leaves[0]) / len(leaves)


@functools.partial(jax.jit, static_argnums=())
def _tree_mean(trees: List[Any]) -> Any:
    return jax.tree_util.tree_map(_mean_leaf, *trees)


def tree_weighted_sum(trees: Sequence[Any], weights: Sequence[float]) -> Any:
    """Weighted sum of param pytrees (weights need not be normalized).

    Raises :class:`ValueError` on an empty or zero-sum weight vector
    (the normalization below would otherwise divide by zero).
    """
    total = _check_weights(weights)
    norm = [w / total for w in weights]

    def _leaf(*leaves):
        dt = leaves[0].dtype
        floating = jnp.issubdtype(dt, jnp.floating)
        acc = leaves[0].astype(jnp.float32) if floating else leaves[0]
        acc = acc * norm[0]
        for leaf, w in zip(leaves[1:], norm[1:]):
            acc = acc + (leaf.astype(jnp.float32) if floating else leaf) * w
        return acc.astype(dt) if floating else acc

    return jax.tree_util.tree_map(_leaf, *trees)


@functools.lru_cache(maxsize=None)
def _packed_reduce_jit(out_dtype_name: str):
    """ONE fused program over the packed wire buffers: zero-init, then a
    per-party multiply-add chain in f32, final divide + cast to the
    output dtype.  The per-element op sequence is exactly the chain the
    streaming aggregator's chunk kernel applies (fl.streaming), which is
    what makes streamed and one-shot aggregation bit-identical."""

    @jax.jit
    def _reduce(bufs, w, total_w):
        acc = jnp.zeros(bufs[0].shape, jnp.float32)
        for i, b in enumerate(bufs):
            acc = acc + w[i] * b.astype(jnp.float32)
        return (acc / total_w).astype(jnp.dtype(out_dtype_name))

    return _reduce


def packed_block_grid(total_elems: int, chunk_elems: Optional[int] = None) -> int:
    """Number of blocks in the packed buffer's canonical chunk grid.

    The grid every fold schedule refers to: ``chunk_elems`` wire
    elements per block (default
    :data:`rayfed_tpu.fl.streaming.DEFAULT_CHUNK_ELEMS`, the transport's
    4 MB bf16 chunk), last block short.  Exported so the streaming
    aggregator, the ring topology (:mod:`rayfed_tpu.fl.ring`) and tests
    all derive the identical grid from the identical constant.
    """
    if chunk_elems is None:
        from rayfed_tpu.fl.streaming import DEFAULT_CHUNK_ELEMS

        chunk_elems = DEFAULT_CHUNK_ELEMS
    if total_elems < 0:
        raise ValueError(f"total_elems must be >= 0, got {total_elems}")
    return max(1, -(-total_elems // int(chunk_elems)))


def packed_stripe_schedule(
    nblocks: int, n_stripes: int
) -> List[List[int]]:
    """Round-robin assignment of the chunk grid to ``n_stripes`` stripes.

    Block ``b`` belongs to stripe ``b % n_stripes``; stripe ``k`` of a
    sorted party ring is owned by the ring's ``k``-th party.  This is
    THE canonical stripe layout (documented in
    ``docs/source/ring_topology.rst``): both the ring reduce-scatter's
    senders and its stripe owners derive it independently, so the
    mapping is part of the cross-party contract, like the wire format.
    """
    if n_stripes < 1:
        raise ValueError(f"n_stripes must be >= 1, got {n_stripes}")
    return [
        list(range(k, nblocks, n_stripes)) for k in range(n_stripes)
    ]


@functools.lru_cache(maxsize=None)
def _stripe_finalize_jit(total_elems: int, out_dtype_name: str):
    @jax.jit
    def fed_finalize_f32(acc, total_w):
        return (acc[:total_elems] / total_w).astype(
            jnp.dtype(out_dtype_name)
        )

    return fed_finalize_f32


def finalize_packed_stripe(acc, total_w: float, total_elems: int, out_dtype):
    """THE packed-aggregate finalize: ``(acc[:n] / total_w).astype(out)``.

    One fused divide + cast over an f32 accumulator holding
    ``sum_i(w_i * x_i)`` — the second half of the (weight·payload,
    weight) pair every fold path carries.  Shared by the one-shot
    reduce, the streaming aggregator, and each ring stripe owner: the
    operation is elementwise, so finalizing a stripe's compacted
    accumulator produces exactly the bytes the whole-buffer finalize
    would produce at those element positions — the keystone of
    ring/coordinator bit-identity.
    """
    return _stripe_finalize_jit(
        int(total_elems), np.dtype(out_dtype).name
    )(acc, np.float32(total_w))


# ---------------------------------------------------------------------------
# Compressed-domain (shared-grid integer) aggregation — the aggregator
# half of the fl.quantize codec/aggregator split.  The sum commutes with
# the shared grid: sum_i w_i*x_i == scale_b*(sum_i w_i*q_i - zp_b*W), so
# the fold is a widening i32 multiply-add over the integer codes and the
# rescale happens ONCE at finalize.  Integer adds are exact and
# associative, which is what makes the streamed, one-shot, ring-striped
# and quorum-subset folds byte-identical by construction.
# ---------------------------------------------------------------------------


def quant_weights(
    weights: Optional[Sequence[float]], n: int
) -> Tuple[List[int], int]:
    """Integer weight vector for the compressed-domain fold.

    The i32 accumulator holds ``sum_i w_i * q_i`` exactly only for
    non-negative **integral** weights (FedAvg example counts are) —
    fractional or negative weights would break both exactness and the
    overflow bound.  Returns ``(per-source ints, total)``; raises
    naming the offending weight otherwise.
    """
    if weights is None:
        return [1] * n, n
    if len(weights) != n:
        raise ValueError(f"{len(weights)} weights for {n} sources")
    out: List[int] = []
    for i, w in enumerate(weights):
        f = float(w)
        if not np.isfinite(f) or f < 0 or f != int(f):
            raise ValueError(
                f"compressed-domain aggregation needs non-negative "
                f"integral weights (example counts); weight {i} is "
                f"{w!r} — pre-scale to integers or use the float path"
            )
        out.append(int(f))
    total = sum(out)
    if total == 0:
        raise ValueError(
            "weights sum to zero — the weighted average is undefined"
        )
    return out, total


@functools.lru_cache(maxsize=None)
def quantized_accum_kernel(chunk_elems: int, wire_dtype: str):
    """One donated-i32-accumulator widening multiply-add step:
    ``acc[off:off+C] += w * widen(q)``.

    The integer sibling of the streaming f32 chunk kernel
    (``fl.streaming._accum_kernel``) and of the per-party chain inside
    :func:`packed_quantized_sum` — integer adds are exact, so all of
    them agree bit-for-bit in ANY fold order, and the single fused
    rescale (:func:`finalize_packed_quantized`) is the only place
    floats appear.

    The jitted function's name is the program's name in a device trace
    (``jit_fed_fold_i32``): the benchmark's ``fold_roofline`` finds the
    fold kernels by it, like ``fed_fold_f32``, ``fed_finalize_*`` and
    ``fed_quant_*``.
    """
    import jax
    import jax.numpy as jnp

    del wire_dtype  # codes widen to i32 whatever the wire width

    @functools.partial(jax.jit, donate_argnums=(0,))
    def fed_fold_i32(acc, chunk, off, w):
        seg = jax.lax.dynamic_slice(acc, (off,), (chunk_elems,))
        return jax.lax.dynamic_update_slice(
            acc, seg + w * chunk.astype(jnp.int32), (off,)
        )

    return fed_fold_i32


@functools.lru_cache(maxsize=None)
def masked_code_kernel():
    """ONE fused weight-and-mask step of a secure round
    (fl.secagg): ``bitcast_i32(u32(w·q) + net_mask)`` over the whole
    code buffer.

    The sibling of :func:`quantized_accum_kernel` on the SENDER side:
    the grid codes widen to i32, fold in this party's own integral
    weight (pairwise masks only cancel at unit fold weight — ``w_i·m −
    w_j·m ≠ 0``), and add the party's net pairwise mask in uint32, whose
    arithmetic wraps mod 2³² by definition (the masked value must be
    uniform over the ring the sum lives in).  The receiver folds the
    resulting i32 codes through the UNCHANGED
    :func:`quantized_accum_kernel` at weight 1 — i32 addition wraps the
    same ring — so after every pair mask met its negative the
    accumulator holds exactly ``Σ w_i·q_i`` and the finalize emits the
    unmasked round's bytes.
    """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def _mask(q, w, net_mask_u32):
        v = w * q.astype(jnp.int32)  # |w·q| ≤ qabs_max·W: exact in i32
        return jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(v, jnp.uint32) + net_mask_u32,
            jnp.int32,
        )

    return _mask


@functools.lru_cache(maxsize=None)
def masked_correction_kernel():
    """Subtract a dropout round's orphaned-mask correction
    (``fl.secagg.mask_correction``) from the donated i32 accumulator —
    uint32 bitcast arithmetic, mod 2³² like every masked step."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=(0,))
    def _sub(acc, corr_u32):
        a = jax.lax.bitcast_convert_type(acc, jnp.uint32)
        return jax.lax.bitcast_convert_type(a - corr_u32, jnp.int32)

    return _sub


@functools.lru_cache(maxsize=None)
def _quant_reduce_jit(nblocks: int, chunk_elems: int):
    """One-shot integer reduce: widen + weighted-add chain over the
    packed code buffers, padded onto the canonical block grid (the
    same padded accumulator shape the streaming fold carries)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def _reduce(bufs, w):
        acc = jnp.zeros(nblocks * chunk_elems, jnp.int32)
        for i, b in enumerate(bufs):
            acc = acc.at[: b.shape[0]].add(w[i] * b.astype(jnp.int32))
        return acc

    return _reduce


@functools.lru_cache(maxsize=None)
def _quant_finalize_jit(chunk_elems: int, total_elems: int,
                        out_dtype_name: str, with_ref: bool):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def fed_finalize_i32(acc, ref, scales, zps, total_w):
        a = acc.reshape(-1, chunk_elems).astype(jnp.float32)
        x = scales[:, None] * (a - zps[:, None] * total_w)
        x = x.reshape(-1)[:total_elems] / total_w
        if with_ref:
            # Delta-coded rounds: the codes summed to W·(mean delta);
            # the shared reference (every party holds it bit-
            # identically) adds back AFTER the divide, elementwise.
            x = ref + x
        return x.astype(jnp.dtype(out_dtype_name))

    return fed_finalize_i32


def finalize_packed_quantized(
    acc, scales, zps, total_w: float, total_elems: int,
    chunk_elems: int, out_dtype, ref=None,
):
    """THE compressed-domain finalize: the single fused rescale
    ``[ref +] (scale_b * (acc - zp_b*W)) / W`` over a block-grid-padded
    i32 accumulator holding ``sum_i w_i * q_i``.

    ``ref`` (delta-coded rounds): the shared reference buffer the codes
    were taken against — a flat f32 array of ``total_elems`` elements
    (a stripe owner passes its stripe-compacted slice), read where it
    lives: a device array is not fetched, a host array is uploaded.

    The quantized sibling of :func:`finalize_packed_stripe`, and like
    it the SINGLE producer of the output bytes for every topology: the
    one-shot reduce, the streaming aggregator, each ring stripe owner
    (with its block-subset ``scales``/``zps`` rows and reference
    slice) and the quorum refold all call exactly this.  Elementwise
    with per-block parameters, so a stripe's rows produce exactly the
    bytes the whole-buffer finalize produces at those element
    positions.
    """
    import jax.numpy as jnp

    from rayfed_tpu.fl.quantize import _flat_f32

    with_ref = ref is not None
    if with_ref:
        ref = _flat_f32(ref)
        if int(ref.size) != int(total_elems):
            raise ValueError(
                f"reference has {ref.size} elements, finalize covers "
                f"{total_elems}"
            )
    else:
        ref = jnp.zeros(0, jnp.float32)
    return _quant_finalize_jit(
        int(chunk_elems), int(total_elems), np.dtype(out_dtype).name,
        with_ref,
    )(acc, ref, np.asarray(scales, np.float32),
      np.asarray(zps, np.float32), np.float32(total_w))


@functools.lru_cache(maxsize=None)
def server_step_kernel(kind: str, hyper: Tuple[float, ...]):
    """ONE fused server-optimization step over packed f32 buffers —
    the aggregate-then-step composition of :mod:`rayfed_tpu.fl.
    server_opt`, placed beside :func:`finalize_packed_stripe` /
    :func:`finalize_packed_quantized` because it consumes exactly their
    output: ``step(x, avg, *state) -> x'`` where ``x`` is the round's
    shared starting buffer, ``avg`` the finalized aggregate and
    ``state`` the packed auxiliary sequence(s).  ``avg`` is deliberately
    NOT donated: the streaming aggregator's result holder retains the
    same buffer (and harnesses step several controller replicas over
    one array) — the donated pass of the aggregate-then-step
    composition is the fold accumulator upstream, and this kernel still
    allocates exactly one output buffer.

    Kinds (hyperparameters are static — one compile per config):

    - ``"momentum"`` ``(lr, momentum)`` — FedAvgM on the packed buffer:
      ``x' = x − lr·(momentum·m + (x − avg))``.  ``lr=1, momentum=0``
      RETURNS ``avg`` literally (bit-exact plain FedAvg, not a
      float-rounded reconstruction of it).
    - ``"fedac"`` ``(lam, gamma, beta)`` — FedAC's linear-coupling
      acceleration (Yuan & Ma 2020) with the round pseudo-gradient
      ``Δ = x − avg``: conservative step ``y' = x − lam·Δ``, aggressive
      step ``z' = z − gamma·Δ`` over the auxiliary sequence ``z``, and
      the broadcast point ``x' = (1−beta)·y' + beta·z'``.  ``lam=1,
      beta=0`` returns ``avg`` literally.

    The step deliberately emits ONLY the new broadcast buffer: the
    state advances via :func:`server_resync_kernel` from the broadcast
    pair ``(x, x')`` on EVERY controller, which is what keeps the
    replicated state byte-identical cluster-wide (see fl.server_opt).
    """
    import jax
    import jax.numpy as jnp

    if kind == "momentum":
        lr, momentum = (float(h) for h in hyper)

        @jax.jit
        def _step(x, avg, m):
            x = x.astype(jnp.float32)
            avg = avg.astype(jnp.float32)
            if momentum == 0.0 and lr == 1.0:
                return avg  # plain FedAvg, bit-exactly
            return x - lr * (momentum * m + (x - avg))

        return _step
    if kind == "fedac":
        lam, gamma, beta = (float(h) for h in hyper)

        @jax.jit
        def _step(x, avg, z):
            x = x.astype(jnp.float32)
            avg = avg.astype(jnp.float32)
            if beta == 0.0 and lam == 1.0:
                return avg  # plain FedAvg, bit-exactly
            delta = x - avg
            y_new = x - lam * delta
            z_new = z - gamma * delta
            return (1.0 - beta) * y_new + beta * z_new

        return _step
    raise ValueError(
        f"unknown server-opt kind {kind!r} — one of 'momentum', 'fedac'"
    )


@functools.lru_cache(maxsize=None)
def server_resync_kernel(kind: str, hyper: Tuple[float, ...]):
    """Advance the packed server-opt state from the round's broadcast
    pair: ``resync(x, x_new, *state) -> new state tuple``.

    The companion of :func:`server_step_kernel`, and the reason every
    controller's state replica stays BYTE-identical with zero extra
    wire bytes: the state is defined as a deterministic f32 function of
    ``(x, x_new, state)`` where ``x_new`` is the round's broadcast —
    the one buffer the whole cluster already byte-agrees on (decoded
    codes in quantized rounds, the f32 broadcast otherwise).  The
    coordinator runs the SAME resync on the same decoded bytes instead
    of keeping its exact-step state, so any downlink quantization error
    is absorbed into the state consistently everywhere (the same
    self-correction an EF residual performs, one level up).  State
    buffers are deliberately NOT donated: FedAC's z₀ aliases the
    caller's initial-point array, and the harnesses/tests retain state
    references across rounds — an aliased donation frees a buffer
    someone else still reads.

    - ``"momentum"``: ``m' = (x − x_new)/lr`` (exactly the step the
      broadcast realized).
    - ``"fedac"``: ``z' = z − (gamma/D)·((1−beta)·x + beta·z − x_new)``
      with ``D = (1−beta)·lam + beta·gamma`` — algebraically
      ``z − gamma·Δ`` with ``Δ`` implied by the realized broadcast.
    """
    import jax
    import jax.numpy as jnp

    if kind == "momentum":
        lr, _momentum = (float(h) for h in hyper)

        # No donation: the old momentum buffer is replaced wholesale
        # without being read, and XLA warns on donated-but-unused.
        @jax.jit
        def _resync(x, x_new, m):
            del m  # replaced wholesale by the realized step
            x = x.astype(jnp.float32)
            x_new = x_new.astype(jnp.float32)
            return ((x - x_new) / lr,)

        return _resync
    if kind == "fedac":
        lam, gamma, beta = (float(h) for h in hyper)
        denom = (1.0 - beta) * lam + beta * gamma

        # No donation on z either: FedAC's z₀ aliases the caller's
        # initial-point array (PackedServerOpt.init), and the state
        # holder/test harnesses may retain references across rounds —
        # one transient f32 buffer is not worth an aliasing hazard.
        @jax.jit
        def _resync(x, x_new, z):
            x = x.astype(jnp.float32)
            x_new = x_new.astype(jnp.float32)
            return (
                z - (gamma / denom) * ((1.0 - beta) * x + beta * z - x_new),
            )

        return _resync
    raise ValueError(
        f"unknown server-opt kind {kind!r} — one of 'momentum', 'fedac'"
    )


def packed_quantized_sum(
    quantized_trees: Sequence[Any],
    weights: Optional[Sequence[float]] = None,
    out_dtype: Any = None,
    ref: Any = None,
):
    """Fused compressed-domain reduce over QuantizedPackedTree
    contributions sharing one grid — the one-shot reference every
    streamed/striped/quorum integer fold is asserted bit-identical to.

    ``ref``: the shared reference buffer for delta-coded contributions
    (``grid.mode == "delta"``) — the finalize adds it back.

    ``out_dtype`` defaults to **float32** (re-coding the mean onto the
    8-bit grid would be exactly the loss no residual compensates; the
    downlink quantizes separately, with its own grid and residual).
    """
    from rayfed_tpu.fl.quantize import QuantizedPackedTree, _check_ref

    packeds = list(quantized_trees)
    if not packeds:
        raise ValueError("packed_quantized_sum needs at least one tree")
    for i, p in enumerate(packeds):
        if not isinstance(p, QuantizedPackedTree):
            raise ValueError(
                f"contribution {i} is not a QuantizedPackedTree (got "
                f"{type(p).__name__}) — quantize with "
                f"fl.quantize.quantize_packed(tree, grid)"
            )
    gmeta = packeds[0].gmeta
    spec = packeds[0].spec
    for i, p in enumerate(packeds[1:], 1):
        if p.gmeta != gmeta or p.spec != spec:
            raise ValueError(
                f"contribution {i} was coded on a different grid "
                f"(fp={p.gmeta.fp:#010x} vs {gmeta.fp:#010x}) — all "
                f"parties must quantize onto the round's shared grid"
            )
    n = len(packeds)
    iw, itotal = quant_weights(weights, n)
    grid = packeds[0].grid()
    grid.check_weight_headroom(itotal)
    ref = _check_ref(grid, ref)
    nblocks = packed_block_grid(gmeta.total_elems, gmeta.chunk_elems)
    acc = _quant_reduce_jit(nblocks, gmeta.chunk_elems)(
        tuple(p.buf for p in packeds),
        np.asarray(iw, np.int32),
    )
    total_w = float(itotal)
    out_name = np.dtype(
        out_dtype if out_dtype is not None else np.float32
    ).name
    buf = finalize_packed_quantized(
        acc, grid.scales, grid.zps, total_w, gmeta.total_elems,
        gmeta.chunk_elems, out_name, ref=ref,
    )
    passthrough = _reduce_passthrough(
        [p.passthrough for p in packeds],
        None if weights is None else list(weights),
        total_w,
    )
    return _packed_result(buf, passthrough, spec, out_name)


def _packed_result(buf, passthrough, spec, out_name):
    """Plain (float) PackedTree around a finalized aggregate buffer."""
    from rayfed_tpu.fl.compression import PackedTree, PackSpec

    if out_name != spec.wire_dtype:
        spec = PackSpec(spec.entries, spec.treedef, out_name)
    return PackedTree(buf, passthrough, spec)


def _reduce_passthrough(passthroughs, weights, total):
    """Average the non-float (passthrough) leaf tuples of N PackedTrees
    with :func:`tree_average`'s per-leaf semantics.  Shared by the
    one-shot (:func:`packed_weighted_sum`) and streaming
    (``fl.streaming``) reduces so the two stay result-identical."""
    if not passthroughs[0]:
        return ()
    if weights is None:
        return tuple(_mean_leaf(*ls) for ls in zip(*passthroughs))
    norm = [float(x) / total for x in weights]

    def _pt(*leaves):
        acc = leaves[0] * norm[0]
        for leaf, wt in zip(leaves[1:], norm[1:]):
            acc = acc + leaf * wt
        return acc

    return tuple(_pt(*ls) for ls in zip(*passthroughs))


def packed_weighted_sum(
    packed_trees: Sequence[Any],
    weights: Optional[Sequence[float]] = None,
    out_dtype: Any = None,
):
    """Fused single-jit reduce over PackedTree contributions.

    Instead of a tree_map over N full trees (one XLA op per leaf per
    tree), the whole model reduces as ONE compiled chain over the packed
    wire buffers — the same math the streaming path
    (:class:`rayfed_tpu.fl.streaming.StreamingAggregator`) applies
    chunk-by-chunk, so the two are bit-identical.  Passthrough
    (non-float) leaves keep the per-leaf averaging semantics of
    :func:`tree_average`.

    ``out_dtype``: dtype of the returned packed buffer — defaults to
    the contributions' wire dtype.  Pass f32 when the aggregate feeds a
    server optimizer or an error-feedback loop: re-quantizing the mean
    to an aggressive wire dtype here is exactly the loss no residual
    would compensate.
    """
    from rayfed_tpu.fl.compression import PackedTree
    from rayfed_tpu.fl.quantize import QuantizedPackedTree

    packeds = list(packed_trees)
    if not packeds:
        raise ValueError("packed_weighted_sum needs at least one tree")
    if any(isinstance(p, QuantizedPackedTree) for p in packeds):
        raise ValueError(
            "packed_weighted_sum got QuantizedPackedTree contributions "
            "— their buffers are integer CODES, not values; fold them "
            "with packed_quantized_sum (the compressed-domain reduce)"
        )
    if not isinstance(packeds[0], PackedTree):
        raise ValueError(
            f"contribution 0 is not a PackedTree "
            f"(got {type(packeds[0]).__name__}) — pack updates with "
            f"fl.compress(tree, packed=True)"
        )
    spec = packeds[0].spec
    for i, p in enumerate(packeds[1:], 1):
        if not isinstance(p, PackedTree) or p.spec != spec:
            raise ValueError(
                f"contribution {i} is not a PackedTree with the same "
                f"spec — all parties must pack the identical structure"
            )
    n = len(packeds)
    if weights is None:
        w = np.ones(n, np.float32)
        total = float(n)
    else:
        if len(weights) != n:
            raise ValueError(f"{len(weights)} weights for {n} trees")
        total = _check_weights(weights)
        w = np.asarray([float(x) for x in weights], np.float32)
    out_name = np.dtype(
        out_dtype if out_dtype is not None else packeds[0].buf.dtype
    ).name
    buf = _packed_reduce_jit(out_name)(
        tuple(p.buf for p in packeds), jnp.asarray(w), np.float32(total)
    )
    passthrough = _reduce_passthrough(
        [p.passthrough for p in packeds], weights, total
    )
    if out_name != spec.wire_dtype:
        from rayfed_tpu.fl.compression import PackSpec

        spec = PackSpec(spec.entries, spec.treedef, out_name)
    return PackedTree(buf, passthrough, spec)


def tree_average(trees: Sequence[Any], weights: Optional[Sequence[float]] = None):
    """Mean (or example-count-weighted mean) of param pytrees.

    PackedTree contributions with a shared spec take the fused
    single-jit reduce (:func:`packed_weighted_sum`): one compiled chain
    over the packed buffers instead of per-leaf dispatches.
    """
    trees = list(trees)
    if not trees:
        raise ValueError("tree_average needs at least one tree")
    if weights is not None and len(weights) != len(trees):
        raise ValueError(f"{len(weights)} weights for {len(trees)} trees")
    from rayfed_tpu.fl.compression import PackedTree
    from rayfed_tpu.fl.quantize import QuantizedPackedTree

    if all(isinstance(t, QuantizedPackedTree) for t in trees):
        if trees[0].gmeta.mode != "abs":
            # Delta codes only mean something against the round's
            # shared reference buffer, which this signature cannot
            # carry — send callers to the explicit reduce.
            raise ValueError(
                "tree_average cannot fold delta-coded "
                "QuantizedPackedTree contributions (the codes are "
                "relative to the round's shared reference) — call "
                "packed_quantized_sum(trees, weights, ref=<shared "
                "reference buffer>) directly"
            )
        return packed_quantized_sum(trees, weights)
    if all(isinstance(t, PackedTree) for t in trees) and all(
        t.spec == trees[0].spec for t in trees[1:]
    ):
        return packed_weighted_sum(trees, weights)
    if weights is None:
        return _tree_mean(trees)
    return tree_weighted_sum(trees, tuple(float(w) for w in weights))


def aggregate(
    fed_objects: Sequence[Any],
    weights: Optional[Sequence[float]] = None,
    *,
    mode: str = "auto",
    coordinator: Optional[str] = None,
    materialize: bool = True,
    reducer: Optional[Any] = None,
):
    """FedAvg round: fetch every party's update and reduce (mean by default).

    ``fed_objects``: one FedObject per party (each owned by its producing
    party).  Every party calls this with the same list at the same point
    in the program, so all parties return the identical averaged tree.

    ``reducer(values) -> tree`` replaces the weighted mean with a custom
    reduction (e.g. :func:`rayfed_tpu.fl.tree_trimmed_mean` or a Krum
    selection) over the round's contributions; it rides the SAME wire
    topology the mean does (coordinator-side execution at N>2, one
    reduce + broadcast), so there is exactly one place that decides who
    talks to whom.  Mutually exclusive with ``weights``.

    Wire topology (``mode``):

    - ``"all_to_all"``: every owner pushes to every peer and each party
      averages locally — N·(N-1) transfers.  Lowest latency at N=2.
    - ``"coordinator"``: contributions go to one party (default: the
      owner of ``fed_objects[0]``), which averages and broadcasts the
      result — 2·(N-1) transfers.  The right shape for N>2.
    - ``"auto"``: coordinator when more than two objects, else
      all-to-all.

    The choice is made from ``len(fed_objects)`` and the argument values
    only — identical on every controller, preserving seq-id determinism.

    ``materialize=False`` (coordinator mode only) returns the averaged
    model as a **FedObject** instead of a value: no ``fed.get`` barrier,
    so consecutive rounds pipeline — pass the returned object straight
    into the next round's ``train.remote(...)`` and the coordinator's
    average/broadcast overlaps the workers' next-round work (the arg
    push replaces broadcast-on-get; same bytes, no driver-side stall).
    Improves on the reference, whose round loop blocks on ``fed.get``
    every round (``tests/test_fed_get.py:47-82`` shape).
    """
    import rayfed_tpu as fed

    if reducer is not None and weights is not None:
        raise ValueError(
            "reducer and weights are mutually exclusive (a custom "
            "reducer defines its own weighting)"
        )

    objs = list(fed_objects)
    if mode == "auto":
        # Pipelined (lazy) rounds only exist in coordinator topology, so
        # materialize=False picks it regardless of party count.
        mode = (
            "coordinator"
            if len(objs) > 2 or not materialize
            else "all_to_all"
        )
    if mode == "all_to_all":
        if not materialize:
            raise ValueError(
                'materialize=False requires mode="coordinator" (all_to_all '
                "averages locally, which must fetch the contributions)"
            )
        values = fed.get(objs)
        if reducer is not None:
            return reducer(values)
        return tree_average(values, weights)
    if mode != "coordinator":
        raise ValueError(f"unknown aggregate mode {mode!r}")

    coord = coordinator or objs[0].get_party()
    w = None if weights is None else tuple(float(x) for x in weights)

    def _reduce(*trees):
        if reducer is not None:
            return reducer(list(trees))
        return tree_average(trees, w)

    avg_obj = fed.remote(_reduce).party(coord).remote(*objs)
    if not materialize:
        return avg_obj
    return fed.get(avg_obj)


class FedAvgActorBase:
    """Template for a party-local training actor (wrap with ``@fed.remote``).

    Holds params (+ optional extra state) on device between rounds;
    subclass or compose with a concrete ``train_step``.  Methods return
    plain pytrees so they cross parties through the tensor wire format.
    """

    def __init__(self, params: Any):
        self._params = params

    def get_params(self) -> Any:
        return self._params

    def set_params(self, params: Any) -> None:
        self._params = params

    def train_local(self, step_fn, batches) -> Any:
        """Run ``step_fn(params, *batch) -> (params, loss)`` over batches."""
        loss = None
        for batch in batches:
            self._params, loss = step_fn(self._params, *batch)
        return self._params, loss
