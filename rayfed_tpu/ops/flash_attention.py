"""Pallas TPU flash attention (tiled online-softmax) with custom VJP.

The MXU wants big tiles streamed through VMEM; materializing the [T, T]
score matrix in HBM wastes the bandwidth that is the usual bottleneck.
This kernel keeps one q tile resident in VMEM and streams k/v tiles
through it, carrying the online-softmax state (running max m, normalizer
l, un-normalized accumulator) in VMEM scratch across the innermost grid
dimension — TPU grids execute sequentially, so scratch persists across
the kv loop.  Matches `rayfed_tpu.ops.attention.dot_product_attention`
numerically (same recurrence as ``blockwise_accumulate``).

Backward is two tiled pallas kernels (dQ and dK/dV) that recompute the
score tile from the saved per-row log-sum-exp — the standard
flash-attention backward formulation, O(T·block) live memory.

Which tiles the three kernels compute is one schedule
(:func:`block_schedule`, counted from the shapes alone): the innermost
grid dimension walks only the band of blocks that hold a visible pair
(:class:`_Band`); a block the diagonal or the band's far edge crosses is
walked in sub-tiles, each skipped, computed unmasked, or masked
(:func:`_dispatch`).  Grouped K/V ``[B, T, KV, D]`` is read by KV head,
never repeated; dK/dV accumulate over a group's query heads in VMEM.

On the CPU backend (the test mode) the kernels run in pallas interpret
mode, so the CPU test mesh exercises the same code path; on any other
backend they are compiled — there is no silent interpreted run on a chip.
"""

from __future__ import annotations

import functools
import operator
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rayfed_tpu import telemetry
from rayfed_tpu.ops.attention import check_score_parts, kv_group

NEG_INF = -1e30


def _interpret_default() -> bool:
    """Interpret mode is the CPU test mode only; every other backend
    compiles the kernel (and raises what the compiler raises)."""
    return jax.default_backend() == "cpu"


# How many pieces each edge of a block that straddles the diagonal or the
# band's far edge is cut into, where the block allows (`_fit_split`).  Not
# a setting: 2 x 2 against the block masked whole, one call at 32 x 128
# heads, 8,192 tokens (`tool/flash_sweep.py --splits 1,2`, my chip run,
# PR 30), forward / dQ / dK/dV ms: window 2,048 on 4 K/V heads 3.08 / 3.47 /
# 4.18 -> 2.66 / 3.00 / 3.57; no window 5.27 / 6.13 / 7.14 -> 5.03 / 5.85 /
# 6.78; window 4,096 on 8 K/V heads 4.33 / 4.94 / 5.95 -> 3.98 / 4.54 / 5.42.
SPLIT = 2

# The checkpoint names of the two residuals only the forward kernel can
# make, `out` [B, T, H, D] and the row statistics `lse` [BH, T] float32
# (`_flash_fwd_bthd`).  A `jax.checkpoint` whose policy saves them
# (`models.llama.REMAT_SAVED`) does not run the forward kernel again in
# its backward pass; it pays `residual_bytes` a call (`B*T*H*D` elements
# of the output's dtype + `B*H*T*4`: 68.2 MB at 1 x 8,192 x 32 x 128 bf16).
RESIDUAL_NAMES = ("flash.out", "flash.lse")

_UNMASKED = (False, False)


def _tile_kinds(diff, rows, cols, window):
    """What a ``rows x cols`` tile needs: ``{(cut_diagonal, cut_band): holds}``.

    ``diff`` is the position of the tile's first query less that of its
    first key, so ``q − k`` runs over ``[lo, hi]`` in the tile and a pair
    is visible where ``0 <= q − k`` (the diagonal) ``< window`` (the
    band's far edge).  A tile for which no entry holds has no visible
    pair; the ``(False, False)`` entry is the tile no edge cuts.  Only
    comparisons and ``&``, so ``diff`` may be a Python int
    (:func:`block_schedule`) or a traced scalar (the kernels); kinds the
    sizes rule out are left out statically.
    """
    lo, hi = diff - (cols - 1), diff + (rows - 1)
    below, across = lo >= 0, (lo < 0) & (hi >= 0)
    if window is None:
        return {_UNMASKED: below, (True, False): across}
    inside, edge = hi < window, (hi >= window) & (lo < window)
    kinds = {(True, False): across & inside, (False, True): below & edge}
    if window > rows + cols - 2:  # wide enough to hold a whole tile
        kinds[_UNMASKED] = below & inside
    if window < rows + cols - 2:  # narrow enough for both edges to cut one
        kinds[(True, True)] = across & edge
    return kinds


def _static_when(holds):
    return lambda body: body() if holds else None


def _static_loop(n, body):
    for t in range(n):
        body(t)


def _traced_loop(n, body):
    jax.lax.fori_loop(0, n, lambda t, _: body(t), None)


def _dispatch(
    compute, when, loop, diff, block_q, block_k, sub_q, sub_k, causal, window
):
    """Run ``compute(rows, cols, edges)`` over what the block at ``diff``
    needs — the ONE tile classification of the three kernels
    (``pl.when`` and a ``fori_loop``) and of :func:`block_schedule` (a
    plain ``if`` and ``for``).

    ``rows``/``cols`` are ``(start, size)`` within the block.  A block no
    edge cuts is one unmasked ``compute`` (``edges`` None); a block with
    no visible pair is skipped; a block an edge cuts is walked in
    ``sub_q x sub_k`` sub-tiles by ONE loop whose body classifies the
    sub-tile from its own scalar ``diff``: skipped, unmasked, or masked
    with ``edges = ((cut_diagonal, cut_band), diff)`` (:func:`_visible`).
    So a kernel traces ``compute`` at most four times however fine the
    cut (whole block; sub-tile unmasked, cut by the diagonal, cut by the
    band's far edge; a fifth only where the window is narrower than a
    sub-tile pair): what a process pays to trace and lower a step does
    not grow with the cut (13 copies of the body cost two cells their
    set-up bound: ledger, PR 29).  A sub-tile's ``start`` is then traced,
    a multiple of its size: every slice is along rows.  The grid step and
    its DMA stay at the block size; the cut's gain is the sub-tiles it
    skips (a masked tile costs 8-20% more than an unmasked one).
    """
    whole = (0, block_q), (0, block_k)
    if not causal:
        compute(*whole, None)
        return

    def classified(d, rows, cols):
        for kind, holds in _tile_kinds(d, rows[1], cols[1], window).items():
            when(holds)(functools.partial(
                compute, rows, cols, None if kind == _UNMASKED else (kind, d)
            ))

    n_q, n_k = block_q // sub_q, block_k // sub_k
    if n_q * n_k == 1:
        classified(diff, *whole)
        return
    kinds = _tile_kinds(diff, block_q, block_k, window)
    if _UNMASKED in kinds:
        when(kinds.pop(_UNMASKED))(functools.partial(compute, *whole, None))

    @when(functools.reduce(operator.or_, kinds.values()))
    def _straddling():
        def sub_tile(t):
            a, b = _divmod(t, n_k)
            q0, k0 = _multiple_of(a * sub_q, sub_q), _multiple_of(b * sub_k, sub_k)
            classified(diff + q0 - k0, (q0, sub_q), (k0, sub_k))

        loop(n_q * n_k, sub_tile)


def _divmod(t, n):
    if isinstance(t, int):
        return divmod(t, n)
    return jax.lax.div(t, n), jax.lax.rem(t, n)  # t >= 0: one equation each


def _multiple_of(x, size):
    return x if isinstance(x, int) else pl.multiple_of(x, size)


def _visible(edges, rows, cols, window):
    """The mask of a tile an edge cuts: one position difference, and
    only the comparison each cutting edge needs (both where one tile
    holds both edges)."""
    (cut_diagonal, cut_band), diff = edges
    row_less_col = jax.lax.broadcasted_iota(
        jnp.int32, (rows, cols), 0
    ) - jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    visible = None
    if cut_diagonal:
        visible = row_less_col >= -diff
    if cut_band:
        band = row_less_col < window - diff
        visible = band if visible is None else visible & band
    return visible


def _across(lanes, cols):
    """A row statistic kept lane-replicated ``(rows, 128)`` (m, l, lse,
    delta), spread over ``cols`` columns: whole copies of its vregs
    where the width allows, which costs no lane broadcast — slicing
    lane 0 and broadcasting it took 11% of a forward call at 8,192
    tokens and 45% at 512 (ISSUE 30, from PR 29's sweep; the 512-token
    forward reads 1.37 -> 0.87 ms with it, my chip run, PR 30) — else
    lane 0 broadcast (head widths and toy blocks under 128)."""
    reps, rest = divmod(cols, lanes.shape[1])
    if rest == 0:
        return jnp.tile(lanes, (1, reps))
    return jnp.broadcast_to(lanes[:, :1], (lanes.shape[0], cols))


def _clip(x, lo, hi):
    if isinstance(x, int):
        return max(lo, min(x, hi))
    return jnp.minimum(jnp.maximum(x, lo), hi)


def _block_of(pos, size, last):
    """The block of ``size`` that holds position ``pos``, clipped to
    ``0..last``.  Three equations where ``pos`` is traced (the floor
    division of a numerator already clipped at 0 needs no sign repair,
    which alone is eleven): the band's arithmetic runs in every kernel
    and index map, and what is traced there a process pays at set-up."""
    if isinstance(pos, int):
        return _clip(pos // size, 0, last)
    return jnp.minimum(jax.lax.div(jnp.maximum(pos, 0), size), last)


class _Band(NamedTuple):
    """Which blocks of the OTHER axis block ``x`` of one axis walks.

    A pair is visible where its difference (``base + x·size + a −
    y·other_size − b`` for element ``a`` of block ``x`` and ``b`` of the
    other axis' block ``y``) lies in ``[lowest, highest]`` (None =
    unbounded), so the blocks that hold one are a run ``first..last``,
    the band, of at most ``steps`` blocks for any ``x``.  The grid's
    innermost dimension is ``steps`` long, not the whole axis: a grid
    step that computes nothing still costs its DMA and about a
    microsecond (1.0-2.1 us a head: ISSUE 30, from PR 29's sweep — a
    third of a window-2,048 call when the grid was the whole square).  ``x`` may be
    a Python int (:func:`block_schedule`) or traced (index maps, kernels).
    """

    size: int
    other_size: int
    n_other: int
    base: int
    lowest: Optional[int]
    highest: Optional[int]
    steps: int

    def _first(self, x, last):
        if self.highest is None or self.n_other == 1:
            return 0
        return _block_of(
            self.base - self.highest + x * self.size, self.other_size, last
        )

    def run(self, x):
        """First and last block of the run, clipped to the axis."""
        end = self.n_other - 1
        if self.lowest is None or end == 0:
            return self._first(x, end), end
        return self._first(x, end), _block_of(
            self.base + self.size - 1 - self.lowest + x * self.size,
            self.other_size, end,
        )

    def block(self, x, step):
        """The block grid step ``step`` of ``x`` is about: the run from
        its first block, shifted down where it would leave the axis
        (the blocks then before the run classify as invisible)."""
        return self._first(x, self.n_other - self.steps) + step

    def fetch(self, x, step):
        """The block to hold in VMEM at that step: ``block`` inside the
        run, the nearest block of the run outside it — an index that
        does not change costs no DMA."""
        first, last = self.run(x)
        return _clip(self.block(x, step), first, last)


def _band(n, size, n_other, other_size, base, lowest, highest) -> _Band:
    band = _Band(size, other_size, n_other, base, lowest, highest, 1)
    runs = [band.run(x) for x in range(n)]
    return band._replace(steps=max(1, max(b - a + 1 for a, b in runs)))


def _bands(t_q, t_k, block_q, block_k, causal, window, q_offset, kv_offset):
    """(the kv blocks a q block walks, the q blocks a kv block walks)."""
    n_q, n_k = t_q // block_q, t_k // block_k
    near = 0 if causal else None  # q − k >= 0
    far = None if window is None else window - 1  # q − k <= window − 1
    return (
        _band(n_q, block_q, n_k, block_k, q_offset - kv_offset, near, far),
        _band(n_k, block_k, n_q, block_q, kv_offset - q_offset,
              None if far is None else -far, near),
    )


class Schedule(NamedTuple):
    """What one head's call computes (:func:`block_schedule`)."""

    grid: Tuple[int, int]  # forward and dQ: (q blocks, kv steps of each)
    grid_dkv: Tuple[int, int]  # dK/dV: (kv blocks, q steps of each)
    sub_q: int  # a sub-tile's rows
    sub_k: int  # and columns
    steps_skipped: int  # forward grid steps that compute nothing
    blocks_unmasked: int  # blocks computed whole, no mask
    tiles_skipped: int  # sub-tiles of straddling blocks: nothing visible,
    tiles_unmasked: int  # every pair visible,
    tiles_masked: int  # an edge crosses
    pairs_visible: int
    pairs_computed: int
    useful_share: float  # visible over computed


def _fit_split(block: int, want: int) -> int:
    """Pieces (<= want) an edge of ``block`` is cut into: each a multiple
    of 512 — a 256-wide sub-tile cost more in fixed work than the pairs
    it leaves out save (PR 29's sweep, before `_across`: dK/dV +6% and
    dQ −3% at 512 tokens, every kernel slower at 8,192; not measured
    again since) — or, for a block under 256 (toy sequences,
    the CPU tests), of 8; 1 where no such cut exists."""
    align = 512 if block >= 256 else 8
    n = max(int(want), 1)
    while n > 1 and block % (n * align):
        n -= 1
    return n


def _sub_tile(block_q: int, block_k: int, sub: int) -> Tuple[int, int]:
    """A sub-tile's (rows, columns) where a block's edges are cut in
    ``sub`` pieces, as far as the blocks allow."""
    return (block_q // _fit_split(block_q, sub),
            block_k // _fit_split(block_k, sub))


def schedule_visits(
    t_q, t_k, block_q, block_k, sub, causal, window, q_offset, kv_offset
):
    """Every rectangle one head's call computes, in the forward grid's
    order: ``(first query row, rows, first key row, cols, masked)``."""
    sub_q, sub_k = _sub_tile(block_q, block_k, sub)
    walk, _ = _bands(
        t_q, t_k, block_q, block_k, causal, window, q_offset, kv_offset
    )
    visits = []
    for qi in range(t_q // block_q):
        for step in range(walk.steps):
            q0, k0 = qi * block_q, walk.block(qi, step) * block_k
            _dispatch(
                lambda rows, cols, edges: visits.append(
                    (q0 + rows[0], rows[1], k0 + cols[0], cols[1],
                     edges is not None)
                ),
                _static_when, _static_loop, (q_offset + q0) - (kv_offset + k0),
                block_q, block_k, sub_q, sub_k, causal, window,
            )
    return visits


def block_schedule(
    t_q, t_k, block_q, block_k, sub, causal, window, q_offset, kv_offset
) -> Schedule:
    """What one head's call does, counted from the shapes alone.

    The kernels walk exactly this (the same :func:`_dispatch` over the
    same :func:`_bands` and :func:`_fit_split`), and the tests hold them
    to :func:`schedule_visits`.  ``sub`` is how many pieces a straddling
    block's edges are cut into where the block allows.
    """
    visits = schedule_visits(
        t_q, t_k, block_q, block_k, sub, causal, window, q_offset, kv_offset
    )
    sub_q, sub_k = _sub_tile(block_q, block_k, sub)
    walk, walk_dkv = _bands(
        t_q, t_k, block_q, block_k, causal, window, q_offset, kv_offset
    )
    grid = (t_q // block_q, walk.steps)
    tiles = [v for v in visits if v[4] or (v[1], v[3]) != (block_q, block_k)]
    straddling = {(v[0] // block_q, v[2] // block_k) for v in tiles}
    masked = sum(v[4] for v in tiles)
    unmasked_blocks = len(visits) - len(tiles)
    pairs_visible = t_q * t_k
    if causal:  # per query: its keys from the band's far edge to itself
        q = q_offset + np.arange(t_q)
        first = kv_offset if window is None else np.maximum(
            kv_offset, q - window + 1
        )
        last = np.minimum(q, kv_offset + t_k - 1)
        pairs_visible = int(np.maximum(last - first + 1, 0).sum())
    pairs_computed = sum(v[1] * v[3] for v in visits)
    return Schedule(
        grid=grid,
        grid_dkv=(t_k // block_k, walk_dkv.steps),
        sub_q=sub_q,
        sub_k=sub_k,
        steps_skipped=grid[0] * grid[1] - unmasked_blocks - len(straddling),
        blocks_unmasked=unmasked_blocks,
        tiles_skipped=(
            len(straddling) * (block_q // sub_q) * (block_k // sub_k)
            - len(tiles)
        ),
        tiles_unmasked=len(tiles) - masked,
        tiles_masked=masked,
        pairs_visible=pairs_visible,
        pairs_computed=pairs_computed,
        useful_share=pairs_visible / max(pairs_computed, 1),
    )


def _scores(q_refs, k_refs, r, c, scale):
    """The score tile of query rows ``r`` and keys ``c``: the sum over
    the score's parts of ``q_p k_p^T``, each product over that part's own
    width, added in VMEM (one part: the usual ``q k^T``), times ``scale``.
    Returns the operands too."""
    qs = [ref[0, r, :] for ref in q_refs]
    ks = [ref[0, c, :] for ref in k_refs]
    s = None
    for q, k in zip(qs, ks):
        part = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = part if s is None else s + part
    return qs, ks, s * scale


def _flash_fwd_kernel(
    # parts x q (1, block_q, d_p); parts x k (1, block_k, d_p);
    # v (1, block_k, d_v); out o (1, block_q, d_v), lse (1, block_q, 128),
    # lane-broadcast so the block is tileable; VMEM f32 acc (block_q, d_v),
    # m and l (block_q, 128)
    *refs,
    parts: int,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    sub_q: int,
    sub_k: int,
    q_offset: int,
    kv_offset: int,
    walk: _Band,
    window=None,
):
    q_refs, k_refs = refs[:parts], refs[parts:2 * parts]
    v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs[2 * parts:]
    qi = pl.program_id(1)
    step = pl.program_id(2)
    ki = walk.block(qi, step)

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # One online-softmax step of the query rows `rows` over the keys
    # `cols` (static slices of the resident blocks).  Offsets are static
    # (compile-time) positions of the first q/kv token.
    def _compute(rows, cols, edges):
        r, c = pl.ds(*rows), pl.ds(*cols)
        # Feed the MXU native-dtype (bf16) operands — casting to f32 first
        # would force f32 matmul passes at a fraction of bf16 throughput.
        # Accumulation is f32 via preferred_element_type.
        _, _, s = _scores(q_refs, k_refs, r, c, scale)  # (rows, cols) f32
        v = v_ref[0, c, :]
        m_prev = m_ref[r, :]  # (rows, 128), lane-replicated: `_across`
        l_prev = l_ref[r, :]
        if edges is not None:
            s = jnp.where(_visible(edges, rows[1], cols[1], window), s, NEG_INF)
        m_cur = jnp.maximum(jnp.max(s, axis=1, keepdims=True), m_prev)
        if edges is None:
            # Every pair visible: the row maximum is a real score, so the
            # guards below have nothing to guard (exp(NEG_INF − m) is 0).
            m_safe, m_from = m_cur, m_prev
        else:
            # Fully-masked rows keep m_cur == NEG_INF; clamp the shift so
            # their p = exp(NEG_INF - 0) == 0 instead of exp(0) == 1 (same
            # guard as attention.blockwise_accumulate).
            m_safe = jnp.where(m_cur <= NEG_INF / 2, 0.0, m_cur)
            m_from = jnp.where(m_prev <= NEG_INF / 2, NEG_INF, m_prev)
        p = jnp.exp(s - _across(m_safe, cols[1]))
        correction = jnp.exp(m_from - m_safe)
        l_cur = l_prev * correction + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[r, :] = (
            acc_ref[r, :] * _across(correction, acc_ref.shape[1]) + pv
        )
        m_ref[r, :] = m_cur
        l_ref[r, :] = l_cur

    _dispatch(
        _compute, pl.when, _traced_loop,
        (q_offset - kv_offset) + qi * block_q - ki * block_k,
        block_q, block_k, sub_q, sub_k, causal, window,
    )

    @pl.when(step == walk.steps - 1)
    def _finalize():
        l_safe = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        o_ref[0] = (
            acc_ref[...] / _across(l_safe, acc_ref.shape[1])
        ).astype(o_ref.dtype)
        lse_ref[0] = (
            m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-37))
        ).astype(lse_ref.dtype)


def _parts(x) -> tuple:
    """``x`` as the tuple of a score's parts: an array is one part."""
    return tuple(x) if isinstance(x, (tuple, list)) else (x,)


def _plan(qs, kvs, block_q, block_k, causal, window, q_offset, kv_offset,
          interpret):
    """What the three ``pallas_call``s share: the checked block sizes
    and sub-tile sizes (the kernels' static arguments), for every array
    of the K/V side (``kvs``: the k parts, then v) the query heads one of
    its heads serves, and the two bands their grids walk."""
    bh, t_q, _ = qs[0].shape
    t_k = kvs[0].shape[1]
    for x in kvs:
        if bh % x.shape[0]:
            raise ValueError(
                f"K/V heads ({x.shape[0]} with the batch) must divide the "
                f"query heads ({bh})"
            )
    groups = tuple(bh // x.shape[0] for x in kvs)
    block_q = min(block_q, t_q)
    block_k = min(block_k, t_k)
    if t_q % block_q or t_k % block_k:
        raise ValueError(
            f"block sizes ({block_q}, {block_k}) must divide the "
            f"sequence lengths ({t_q}, {t_k})"
        )
    if not interpret and (block_q % 8 or block_k % 8):
        raise ValueError(
            f"TPU tiling requires block sizes divisible by 8, got "
            f"({block_q}, {block_k})"
        )
    sub_q, sub_k = _sub_tile(block_q, block_k, SPLIT)
    common = dict(
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        sub_q=sub_q,
        sub_k=sub_k,
        q_offset=q_offset,
        kv_offset=kv_offset,
        window=window,
    )
    walk, walk_dkv = _bands(
        t_q, t_k, block_q, block_k, causal, window, q_offset, kv_offset
    )
    return common, groups, walk, walk_dkv


def _band_specs(block_q, block_k, walk: _Band):
    """The block specs of the forward and dQ grids ``(query head, q
    block, kv step)``: ``of_q(width)`` for an array of the query side,
    ``of_kv(x, group)`` for one of the K/V side, each of whose heads
    serves ``group`` query heads and whose block is the band's."""
    def of_q(width):
        return pl.BlockSpec((1, block_q, width), lambda b, i, s: (b, i, 0))

    def of_kv(x, group):
        return pl.BlockSpec(
            (1, block_k, x.shape[-1]),
            lambda b, i, s: (jax.lax.div(b, group), walk.fetch(i, s), 0),
        )

    return of_q, of_kv


# Each wrapper of a ``pallas_call`` is a ``jax.jit`` of its own, keyed by
# the static arguments: a process traces each distinct kernel ONCE, and
# the jaxpr is then found again by the forward that a checkpoint
# recomputes, by both branches of a ``cond`` over layer kinds, by every
# scanned group and by every party's thread.  Tracing a kernel is host
# Python that runs before the compile cache can be asked, under one GIL
# for all the parties of a process (ledger, PR 29: +11.8 s of set-up
# with four parties; `tool/flash_sweep.py --lowering`).
_STATIC = (
    "scale", "causal", "block_q", "block_k", "q_offset", "kv_offset",
    "interpret", "out_dtype", "window",
)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _flash_forward(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    q_offset: int,
    kv_offset: int,
    interpret: bool,
    out_dtype=None,
    window=None,
):
    """Run the pallas kernel on q [BH, T, D], k [B·KV, T, D], v [B·KV,
    T, Dv] inputs; returns (o [BH, T, Dv], lse).  Query head ``h`` reads
    K/V head ``h // (H // KV)`` (the index map's ``b // group``): grouped
    K/V is never repeated.  ``q`` and ``k`` may be tuples of score PARTS
    (``q_p`` [BH, T, D_p], ``k_p`` [B·KV_p, T, D_p]): the score is the sum
    of the parts' products, each part of k read by its own head count.

    ``out_dtype`` overrides the output dtype of ``o`` (default: q's) —
    ring callers take f32 so per-step partials are not rounded to bf16
    before the cross-step merge.

    The head dim is used directly as the block lane dim — Mosaic pads
    sub-128 tiles internally, which beats explicitly zero-padding to 128
    (that would double HBM traffic and MXU passes for d=64).  The lse
    output is lane-broadcast to (bh, t_q, 128) so its block satisfies
    the TPU (8, 128) tiling rule, then lane 0 is taken.
    """
    qs, ks = _parts(q), _parts(k)
    bh, t_q, _ = qs[0].shape
    d_v = v.shape[-1]
    common, groups, walk, _ = _plan(
        qs, ks + (v,), block_q, block_k, causal, window, q_offset, kv_offset,
        interpret,
    )
    block_q, block_k = common["block_q"], common["block_k"]
    of_q, of_kv = _band_specs(block_q, block_k, walk)

    with jax.named_scope("flash.fwd"):
        o, lse = pl.pallas_call(
            functools.partial(
                _flash_fwd_kernel, parts=len(qs), scale=scale, walk=walk,
                **common,
            ),
            grid=(bh, t_q // block_q, walk.steps),
            in_specs=[of_q(x.shape[-1]) for x in qs]
            + [of_kv(x, g) for x, g in zip(ks + (v,), groups)],
            out_specs=[of_q(d_v), of_q(128)],
            out_shape=[
                jax.ShapeDtypeStruct((bh, t_q, d_v), out_dtype or qs[0].dtype),
                jax.ShapeDtypeStruct((bh, t_q, 128), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, d_v), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
            ],
            interpret=interpret,
        )(*qs, *ks, v)
    return o, lse[..., 0]


def _backward_tile(q_refs, k_refs, v_ref, do_ref, lse_ref, delta_ref, rows,
                   cols, edges, scale, window):
    """What both backward kernels recompute of a tile: its operands (a
    list a score part for q and k), ``p`` (zeroed where invisible by the
    mask's own predicate) and ``ds = p ∘ (dO Vᵀ − D)``."""
    r, c = pl.ds(*rows), pl.ds(*cols)
    # Native-dtype (bf16) MXU operands, f32 accumulation — see fwd.
    q, k, s = _scores(q_refs, k_refs, r, c, scale)  # (rows, cols)
    v = v_ref[0, c, :]
    do = do_ref[0, r, :]
    p = jnp.exp(s - _across(lse_ref[0, r, :], cols[1]))
    if edges is not None:
        # Fully-masked rows have lse ~ NEG_INF and p = inf there — every
        # pair of such a row is invisible, so the select zeroes it too.
        p = jax.lax.select(
            _visible(edges, rows[1], cols[1], window), p, jnp.zeros_like(p)
        )
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - _across(delta_ref[0, r, :], cols[1]))
    return q, k, do, p, ds


def _flash_bwd_dq_kernel(
    # parts x q (1, block_q, d_p); parts x k (1, block_k, d_p);
    # v (1, block_k, d_v); do (1, block_q, d_v); lse, delta (1, block_q, 128);
    # out parts x dq (1, block_q, d_p); VMEM f32 parts x (block_q, d_p)
    *refs,
    parts: int,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    sub_q: int,
    sub_k: int,
    q_offset: int,
    kv_offset: int,
    walk: _Band,
    window=None,
):
    """dQ = (P ∘ (dO Vᵀ − D)) K · scale (a score part: its own K),
    accumulated over the kv blocks of the q block's band."""
    q_refs, k_refs = refs[:parts], refs[parts:2 * parts]
    v_ref, do_ref, lse_ref, delta_ref = refs[2 * parts:2 * parts + 4]
    dq_refs, acc_refs = refs[2 * parts + 4:3 * parts + 4], refs[3 * parts + 4:]
    qi = pl.program_id(1)
    step = pl.program_id(2)
    ki = walk.block(qi, step)

    @pl.when(step == 0)
    def _init():
        for acc_ref in acc_refs:
            acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute(rows, cols, edges):
        _, ks, _, _, ds = _backward_tile(
            q_refs, k_refs, v_ref, do_ref, lse_ref, delta_ref, rows, cols,
            edges, scale, window,
        )
        ds = ds.astype(ks[0].dtype)
        for acc_ref, k in zip(acc_refs, ks):
            acc_ref[pl.ds(*rows), :] += jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    _dispatch(
        _compute, pl.when, _traced_loop,
        (q_offset - kv_offset) + qi * block_q - ki * block_k,
        block_q, block_k, sub_q, sub_k, causal, window,
    )

    @pl.when(step == walk.steps - 1)
    def _finalize():
        for dq_ref, acc_ref in zip(dq_refs, acc_refs):
            dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    # parts x q (1, block_q, d_p); parts x k (1, block_k, d_p);
    # v (1, block_k, d_v); do (1, block_q, d_v); lse, delta (1, block_q, 128);
    # out parts x dk (1, block_k, d_p), dv (1, block_k, d_v); VMEM f32
    # accumulators of the outputs' shapes
    *refs,
    parts: int,
    spans: tuple,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    sub_q: int,
    sub_k: int,
    q_offset: int,
    kv_offset: int,
    walk: _Band,
    window=None,
):
    """dV = Pᵀ dO and dK = dSᵀ Q · scale (a score part: its own Q),
    accumulated over the innermost grid dimension: the query heads of the
    K/V side's COARSEST head, and for each the q blocks of the kv
    block's band.  ``spans`` (a k part, then v): None where the array's
    head is that coarsest one, so its gradient sums over the whole
    dimension (grouped K/V; a rotary key all query heads share); else
    the steps one of its heads stays, after which its gradient is written
    and its accumulator starts again."""
    q_refs, k_refs = refs[:parts], refs[parts:2 * parts]
    v_ref, do_ref, lse_ref, delta_ref = refs[2 * parts:2 * parts + 4]
    out_refs = refs[2 * parts + 4:3 * parts + 5]  # parts x dk, dv
    acc_refs = refs[3 * parts + 5:]
    dk_acc_refs, dv_acc_ref = acc_refs[:parts], acc_refs[parts]
    ki = pl.program_id(1)
    step = pl.program_id(2)
    qi = walk.block(ki, jax.lax.rem(step, walk.steps))

    def at_end(span, last):
        """Is this the first (``last``: the last) step of a head that
        stays ``span`` steps."""
        if span is None:
            return step == (pl.num_programs(2) - 1 if last else 0)
        return jax.lax.rem(step, span) == (span - 1 if last else 0)

    grads = list(zip(spans, acc_refs, out_refs, (scale,) * parts + (None,)))
    for span in dict.fromkeys(spans):
        @pl.when(at_end(span, False))
        def _init(span=span):
            for of, acc_ref, _, _ in grads:
                if of == span:
                    acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute(rows, cols, edges):
        qs, _, do, p, ds = _backward_tile(
            q_refs, k_refs, v_ref, do_ref, lse_ref, delta_ref, rows, cols,
            edges, scale, window,
        )
        c = pl.ds(*cols)
        dv_acc_ref[c, :] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # pᵀ @ do: (cols, d_v)
        ds = ds.astype(qs[0].dtype)
        for dk_acc_ref, q in zip(dk_acc_refs, qs):
            dk_acc_ref[c, :] += jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # dsᵀ @ q (un-normalized; scale applied at finalize)

    _dispatch(
        _compute, pl.when, _traced_loop,
        (q_offset - kv_offset) + qi * block_q - ki * block_k,
        block_q, block_k, sub_q, sub_k, causal, window,
    )

    for span in dict.fromkeys(spans):
        @pl.when(at_end(span, True))
        def _finalize(span=span):
            for of, acc_ref, out_ref, by in grads:
                if of == span:
                    acc = acc_ref[...] if by is None else acc_ref[...] * by
                    out_ref[0] = acc.astype(out_ref.dtype)


def _lse_delta_lanes(o, lse, do):
    """Lane-broadcast (lse, delta) to (bh, t_q, 128) for the bwd kernels.

    ``delta = rowsum(dO ∘ O)``; both depend only on (o, lse, do), so ring
    callers hoist this out of their per-step loop.
    """
    bh, t_q, _ = o.shape
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    )  # (bh, t_q)
    lse_b = jnp.broadcast_to(lse[..., None], (bh, t_q, 128))
    delta_b = jnp.broadcast_to(delta[..., None], (bh, t_q, 128))
    return lse_b, delta_b


@functools.partial(jax.jit, static_argnames=_STATIC)
def _flash_backward_pallas(
    q, k, v, o, lse, do, *, scale: float, causal: bool,
    block_q: int, block_k: int, q_offset: int, kv_offset: int, interpret: bool,
    lse_delta_b=None, out_dtype=None, window=None,
):
    """Pallas flash backward on q [BH, T, D], k [B·KV, T, D], v [B·KV, T,
    Dv] inputs (``q``/``k`` may be tuples of score parts, see
    :func:`_flash_forward`) → (dq, dk, dv), each of its input's shape
    and structure.

    ``out_dtype`` overrides the gradients' dtype (default: the inputs') —
    ring callers take f32 so per-step partials are not rounded to bf16
    before cross-step accumulation.

    Two tiled kernels: dQ iterates kv blocks innermost (accumulator over
    the q row block), dK/dV iterates innermost over the q blocks of every
    query head of its K/V head's group (accumulators over the kv block,
    written once).  ``delta = rowsum(dO ∘ O)`` and the saved lse are
    lane-broadcast to 128 so their blocks satisfy TPU (8, 128) tiling;
    pass ``lse_delta_b`` (from :func:`_lse_delta_lanes`) to reuse them
    across calls that share (o, lse, do).
    """
    qs, ks = _parts(q), _parts(k)
    kvs = ks + (v,)
    bh, t_q, _ = qs[0].shape
    t_k, d_v = v.shape[1], v.shape[-1]
    common, groups, walk, walk_dkv = _plan(
        qs, kvs, block_q, block_k, causal, window, q_offset, kv_offset,
        interpret,
    )
    block_q, block_k = common["block_q"], common["block_k"]
    if lse_delta_b is None:
        lse_delta_b = _lse_delta_lanes(o, lse, do)
    lse_b, delta_b = lse_delta_b
    of_q, of_kv = _band_specs(block_q, block_k, walk)

    q_specs = [of_q(x.shape[-1]) for x in qs]
    with jax.named_scope("flash.dq"):
        dq = pl.pallas_call(
            functools.partial(
                _flash_bwd_dq_kernel, parts=len(qs), scale=scale, walk=walk,
                **common,
            ),
            grid=(bh, t_q // block_q, walk.steps),
            in_specs=q_specs + [of_kv(x, g) for x, g in zip(kvs, groups)]
            + [of_q(d_v), of_q(128), of_q(128)],
            out_specs=q_specs,
            out_shape=[
                jax.ShapeDtypeStruct(x.shape, out_dtype or x.dtype) for x in qs
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, x.shape[-1]), jnp.float32) for x in qs
            ],
            interpret=interpret,
        )(*qs, *kvs, do, lse_b, delta_b)

    # Grid (the K/V side's coarsest head, kv block, that head's query
    # heads x their q steps).  An array of the K/V side with more heads
    # (``wide`` of them a coarsest head) moves to its next head every
    # ``group * q_steps`` steps.
    num_k, q_steps = t_k // block_k, walk_dkv.steps
    most = max(groups)

    def of_q(width):
        return pl.BlockSpec(
            (1, block_q, width),
            lambda b, j, s: (
                b * most + jax.lax.div(s, q_steps),
                walk_dkv.fetch(j, jax.lax.rem(s, q_steps)),
                0,
            ),
        )

    def of_kv(x, group):
        if group == most:
            return pl.BlockSpec((1, block_k, x.shape[-1]), lambda b, j, s: (b, j, 0))
        wide, span = most // group, group * q_steps
        return pl.BlockSpec(
            (1, block_k, x.shape[-1]),
            lambda b, j, s: (b * wide + jax.lax.div(s, span), j, 0),
        )

    kv_specs = [of_kv(x, g) for x, g in zip(kvs, groups)]
    with jax.named_scope("flash.dkv"):
        *dk, dv = pl.pallas_call(
            functools.partial(
                _flash_bwd_dkv_kernel, parts=len(qs), scale=scale,
                walk=walk_dkv, spans=tuple(
                    None if g == most else g * q_steps for g in groups
                ), **common,
            ),
            grid=(bh // most, num_k, most * q_steps),
            in_specs=[of_q(x.shape[-1]) for x in qs] + kv_specs
            + [of_q(d_v), of_q(128), of_q(128)],
            out_specs=kv_specs,
            out_shape=[
                jax.ShapeDtypeStruct(x.shape, out_dtype or x.dtype) for x in kvs
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, x.shape[-1]), jnp.float32) for x in kvs
            ],
            interpret=interpret,
        )(*qs, *kvs, do, lse_b, delta_b)

    if isinstance(q, (tuple, list)):
        return tuple(dq), tuple(dk), dv
    return dq[0], dk[0], dv


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10)
)
def _flash_bthd(
    q, k, v, scale, causal, block_q, block_k, q_offset, kv_offset, interpret,
    window,
):
    out, _ = _flash_out_lse(
        q, k, v, scale, causal, block_q, block_k, q_offset, kv_offset,
        interpret, window,
    )
    return out


def _bthd_to_bht(x):  # [B,T,H,D] -> [B*H, T, D]
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _bht_to_bthd(x, b, h):  # [B*H, T, D] -> [B,T,H,D]
    bh, t, d = x.shape
    return x.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def _flash_out_lse(
    q, k, v, scale, causal, block_q, block_k, q_offset, kv_offset, interpret,
    window,
):
    b, _, h, _ = jax.tree_util.tree_leaves(q)[0].shape
    to_bht = functools.partial(jax.tree_util.tree_map, _bthd_to_bht)
    o, lse = _flash_forward(
        to_bht(q),
        to_bht(k),
        _bthd_to_bht(v),
        scale=scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        q_offset=q_offset,
        kv_offset=kv_offset,
        interpret=interpret,
        window=window,
    )
    return _bht_to_bthd(o, b, h), lse


def _flash_fwd_bthd(
    q, k, v, scale, causal, block_q, block_k, q_offset, kv_offset, interpret,
    window,
):
    out, lse = _flash_out_lse(
        q, k, v, scale, causal, block_q, block_k, q_offset, kv_offset,
        interpret, window,
    )
    # The names are on the forward RULE's residuals (outside a
    # checkpoint they are the identity): `out` after the transpose back
    # to [B, T, H, Dv] and the [BH, T] float32 `lse`, never its
    # lane-replicated [BH, T, 128] form.
    out = checkpoint_name(out, RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse, RESIDUAL_NAMES[1])
    return out, (q, k, v, out, lse)


def _flash_bwd_bthd(
    scale, causal, block_q, block_k, q_offset, kv_offset, interpret, window,
    res, g,
):
    q, k, v, out, lse = res
    b = out.shape[0]
    to_bht = functools.partial(jax.tree_util.tree_map, _bthd_to_bht)
    dq, dk, dv = _flash_backward_pallas(
        to_bht(q),
        to_bht(k),
        _bthd_to_bht(v),
        _bthd_to_bht(out),
        lse,
        _bthd_to_bht(g),
        scale=scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        q_offset=q_offset,
        kv_offset=kv_offset,
        interpret=interpret,
        window=window,
    )
    # Every gradient back in its input's [B, T, heads, width].
    like = lambda grads, xs: jax.tree_util.tree_map(
        lambda d, x: _bht_to_bthd(d, b, x.shape[2]), grads, xs
    )
    return like(dq, q), like(dk, k), like(dv, v)


_flash_bthd.defvjp(_flash_fwd_bthd, _flash_bwd_bthd)


def flash_attention(
    q,  # an array, or a tuple of score parts
    k,
    v: jax.Array,
    *,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    # 1024/1024: a block is the unit of DMA and of the grid.  With no
    # window (36 computed blocks a head, 32 x 128 heads, 8,192 tokens,
    # bf16; `tool/flash_sweep.py` on a v5e, my chip run, PR 30, blocks
    # masked whole) a block costs 4.6 us a head forward, 5.3 dQ, 6.2 dK/dV
    # (2.7, 4.1, 5.4 us of matmuls at peak); a grid step that computes
    # nothing cost 1.0-2.1 us while it still fetched its block (PR 29's
    # sweep), hence the band.  Blocks of 512 (the 16 x 512 shape) cost 1.7
    # us a head where a quarter of the work would be 1.1-1.6.  Bigger
    # (1024/2048) exceeds the 16 MB scoped-VMEM limit in dK/dV.
    block_q: int = 1024,
    block_k: int = 1024,
    q_offset: int = 0,
    kv_offset: int = 0,
    mask: Optional[jax.Array] = None,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Tiled flash attention, BTHD layout — drop-in for
    :func:`rayfed_tpu.ops.attention.dot_product_attention` (also as the
    ``attn_fn`` of Ulysses attention).

    ``k``/``v`` are ``[B, T, KV, D]`` with ``KV`` dividing ``q``'s ``H``
    heads (grouped-query attention): query head ``h`` reads K/V head
    ``h // (H // KV)`` straight from the unrepeated arrays, and dK/dV
    come back ``[B, T, KV, D]``, summed over each group in the kernel.

    Widths come from the shapes.  ``v`` may be ``[B, T, KV, Dv]`` with a
    width of its own: the output is ``[B, T, H, Dv]``, and nothing is
    padded to the query-key width.  ``q`` and ``k`` may be tuples of
    score PARTS, ``q_p`` ``[B, T, H, D_p]`` and ``k_p`` ``[B, T, KV_p,
    D_p]``: the score is the sum over parts of ``q_p · k_p``, added in
    VMEM, each ``k_p`` read by its own head count (latent attention: a
    per-head part, and a rotary part ONE key head holds for all query
    heads, never copied to them); the default scale is over the summed
    width, and the gradients come back in the inputs' structure, a
    shared part's summed over its query heads in the kernel.

    ``q_offset``/``kv_offset`` are *static* global positions of the first
    q/kv token (sharded-causal use).  Arbitrary dense ``mask`` is not
    supported by the tiled kernel — use ``dot_product_attention``.
    ``interpret=None`` selects the pallas interpreter on the CPU backend
    only, so the same code path runs on the CPU test mesh.

    ``window`` (static, requires ``causal=True``): sliding-window
    attention — query q sees keys in ``(q − window, q]`` (Mistral
    style).  kv blocks entirely outside the band are skipped, so FLOPs
    scale with O(T·window) instead of the causal triangle.
    """
    if mask is not None:
        raise ValueError(
            "flash_attention does not support a dense mask; use "
            "dot_product_attention (or causal=True with offsets)"
        )
    if window is not None:
        if not causal:
            raise ValueError("window= requires causal=True (Mistral SWA)")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    if isinstance(q, (tuple, list)):
        q, k = tuple(q), tuple(k)
        check_score_parts(q, k, v)  # widths pair up, head counts divide
    else:
        kv_group(q, k, v)  # K/V heads must agree and divide the query heads
    qs, ks = _parts(q), _parts(k)
    if interpret is None:
        interpret = _interpret_default()
    qk_widths = [x.shape[-1] for x in qs]
    scale = sm_scale if sm_scale is not None else sum(qk_widths) ** -0.5
    # Blocks must divide the sequence lengths: shrink the requested size
    # to the largest 8-aligned divisor (e.g. T=1280 with block_k=1024 →
    # 640) instead of erroring on any non-multiple length.
    b, t_q, h, _ = qs[0].shape
    t_k = ks[0].shape[1]
    block_q = _fit_block(t_q, block_q)
    block_k = _fit_block(t_k, block_k)
    if not interpret and (block_q % 8 or block_k % 8):
        # No 8-aligned divisor exists (e.g. prime T): fail here with an
        # actionable message instead of a Mosaic tiling error downstream.
        raise ValueError(
            f"sequence lengths ({t_q}, {t_k}) admit no "
            f"8-aligned block split for the compiled TPU kernel — pad the "
            f"sequence to a multiple of 8 or use dot_product_attention"
        )
    q_offset, kv_offset = int(q_offset), int(kv_offset)
    window = None if window is None else int(window)
    if telemetry.armed():
        # One record a call traced while the recorder is armed: what the
        # kernels of this call (forward and backward) compute.
        schedule = block_schedule(
            t_q, t_k, block_q, block_k, SPLIT, causal, window,
            q_offset, kv_offset,
        )
        telemetry.emit(
            "attn.schedule",
            detail=dict(
                schedule._asdict(), batch=b, heads=h,
                kv_heads=ks[0].shape[2], t_q=t_q, t_k=t_k,
                block_q=block_q, block_k=block_k, causal=causal,
                window=window, q_offset=q_offset, kv_offset=kv_offset,
                # the width of each score part (and the K heads that hold
                # it), and of the values
                qk_widths=qk_widths, part_kv_heads=[x.shape[2] for x in ks],
                v_width=v.shape[-1],
                # what a checkpoint that saves RESIDUAL_NAMES keeps a
                # call: the output (the VALUE width) and a float32
                # statistic a (batch, head, query)
                residual_bytes=b * t_q * h * v.shape[-1] * qs[0].dtype.itemsize
                + b * h * t_q * 4,
            ),
        )
    return _flash_bthd(
        q, k, v, scale, causal, block_q, block_k, q_offset, kv_offset,
        interpret, window,
    )


def _fit_block(t: int, want: int) -> int:
    """Largest block <= want that divides t (8-aligned when possible)."""
    b = min(want, t)
    while b > 8 and (t % b or b % 8):
        b -= 8
    if t % b == 0:
        return b
    while b > 1 and t % b:  # tiny/odd sequence lengths (tests)
        b -= 1
    return max(b, 1)
