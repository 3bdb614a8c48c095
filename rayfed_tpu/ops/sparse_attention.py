"""Trainable block-sparse attention (InfLLM-v2: arXiv:2509.24663; the
MiniCPM4 report, arXiv:2506.07900, section 2.1): every query attends to
the key blocks its K/V group SELECTED, and to nothing else.

The selection, per K/V head ``g`` and query ``t`` (:func:`select_mask`,
plain XLA, no gradient: the choice is discrete)::

    K~_j     = mean(k[s j : s j + w])           compressed keys, stride s, width w
    p_{t,j}  = sum over the query heads h of g of softmax_j(q_{t,h} . K~_j / sqrt(d))
               over the windows that end at or before t
    score_m  = max of p_{t,j} over the windows that overlap block m
    S_t      = the first ``init_blocks`` blocks, the blocks of the last
               ``window_size`` tokens, and the highest-scoring causal
               blocks left, ``topk`` blocks in all, equal scores to the
               lower block (``jax.lax.top_k``'s set)

``S_t`` is a set and is kept as a mask over the blocks: no sort ranks
them (:func:`top_mask` finds the ``topk``-th score by bisection).

and the attention (:func:`sparse_attention`, a Pallas kernel pair with a
hand-written backward)::

    o_{t,h} = softmax over keys i <= t in the blocks of S_t of (q_{t,h} . k_i / sqrt(d)) v_i

**The kernel's key blocks are data.**  It walks tiles of ``tile``
queries by ``tile`` keys.  Which key tiles a query tile visits is the
union of its tokens' selections, handed over by scalar prefetch as ONE
list a K/V head (:func:`visit_lists`: every (query tile, key tile) pair
any token of the query tile selected a block of, in order, padded by
repeating the last pair so that a padded grid step fetches nothing);
the grid is as long as the causal tile pairs, the most a list can hold.
Inside a pair each token's own selection masks the scores: a word a
token and key tile (:func:`block_words`) whose bit ``b`` says "block
``b`` of this key tile is selected", and causality within the diagonal
tile.  So the kernel is exact: a query sees its own ``S_t`` and nothing
else, whatever its neighbours chose.  The forward and the dQ grids are
(query head, pair) with the pairs in query-tile order; the dK/dV grid is
(K/V head, pair, query head of the group) with the pairs in key-tile
order, accumulating a key tile's gradients over every query tile and
head that visited it.

The output and the row statistics carry the flash kernel's checkpoint
names (``flash_attention.RESIDUAL_NAMES``), and the selection's arrays
:data:`SELECTION_NAME`: a rematerialized layer keeps them
(``llama.REMAT_SAVED``) and runs neither the selection nor the forward
kernel again; a selection made again from recomputed scores need not be
the one the forward pass used.

On the CPU backend the kernels run in Pallas interpret mode (the test
mode); compiled, the tile must be a multiple of 128 and of the block.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rayfed_tpu import telemetry

# `_interpret_default` is read through the module at each call (one
# patch of it steers every kernel of the package).
_flash = importlib.import_module("rayfed_tpu.ops.flash_attention")

# The checkpoint name of the selection's arrays (the words and the visit
# lists the kernels read).
SELECTION_NAME = "attn.selected"
_BITS = 24  # blocks a word holds (float32 keeps 24 bits whole)


@dataclasses.dataclass(frozen=True)
class SparseConfig:
    """InfLLM-v2's sizes (the MiniCPM4 family's ``sparse_config``), and
    two of the program's own: the kernel's ``tile`` (queries and keys a
    grid step) and the queries the selection scores at once
    (``select_chunk``), each cut to the sequence where that is shorter
    (the tile by :meth:`tile_for`)."""

    kernel_size: int = 32  # a compressed key's width
    kernel_stride: int = 16  # and stride
    block_size: int = 64  # a selected block's keys
    topk: int = 64  # blocks a query attends to
    init_blocks: int = 1  # leading blocks every query selects
    window_size: int = 2048  # local tokens every query selects
    dense_len: int = 8192  # up to here the layer is dense attention
    tile: int = 512
    select_chunk: int = 1024

    def __post_init__(self):
        if self.kernel_size % self.kernel_stride or (
            self.block_size % self.kernel_stride
        ):
            raise ValueError(
                "the compressed keys' stride must divide their width and "
                "the block"
            )
        if self.window_size < 1:
            raise ValueError("a query's selection must hold its own block")

    def tile_for(self, t: int) -> int:
        """The kernel's tile for ``t`` tokens: ``tile``, or the whole
        sequence where that is shorter; it must hold whole blocks, at
        most ``_BITS`` of them, and the sequence whole tiles."""
        tile = min(self.tile, t)
        per_tile = tile // self.block_size
        if tile % self.block_size or not 0 < per_tile <= _BITS or t % tile:
            raise ValueError(
                f"{t} tokens in tiles of {tile}: a tile must hold whole "
                f"blocks of {self.block_size}, at most {_BITS} of them, "
                f"and the sequence whole tiles"
            )
        return tile


# -- the selection --------------------------------------------------------


def compress_keys(k, kernel: int, stride: int):
    """``K~`` [B, windows, KV, D] float32: the mean of each window of
    ``kernel`` keys at a ``stride``, the windows that lie inside the
    sequence (``(T - kernel) // stride + 1``)."""
    b, t, kv, d = k.shape
    pieces = kernel // stride
    n = (t - kernel) // stride + 1
    sums = k[:, : (t // stride) * stride].astype(jnp.float32).reshape(
        b, t // stride, stride, kv, d
    ).sum(axis=2)
    total = sum(sums[:, u: u + n] for u in range(pieces))
    return total / kernel


def _order_keys(score):
    """``score`` float32 as uint32 keys in the same total order that
    ``jax.lax.top_k`` ranks by (the low 31 bits of a negative flipped,
    then the sign bit), ``-inf`` at 0, the lowest: a key above 0 is a
    block that may be selected."""
    bits = jax.lax.bitcast_convert_type(score, jnp.uint32)
    negative = jax.lax.shift_right_logical(bits, jnp.uint32(31)) == 1
    key = jnp.where(negative, ~bits, bits | jnp.uint32(1 << 31))
    return jnp.where(score > -jnp.inf, key, jnp.uint32(0))


def threshold_passes(nblocks: int) -> int:
    """Compare-and-count passes over a row of ``nblocks`` keys that
    :func:`top_mask` makes: 32 for the threshold's bits, then those of
    the highest block index for the tie."""
    return 32 + max(nblocks - 1, 0).bit_length()


def top_mask(score, k: int):
    """``(mask, tied)``: ``mask`` [..., N] bool is true for the blocks
    ``jax.lax.top_k(score, k)`` returns with a value above ``-inf``,
    without a sort: the k-th largest key by bisection on its bits (the
    largest ``t`` with at least ``k`` keys ``>= t``), every key above
    it, and of the keys equal to it the lowest block indices, by
    bisection on the index bound, until there are ``k`` (``top_k``'s own
    rule for ties); where fewer than ``k`` keys lie above ``-inf``, all
    of them.  ``tied`` [...] marks rows where a block equal to the
    threshold was left out: the index decided."""
    key = _order_keys(score)
    count = lambda hit: jnp.sum(hit, axis=-1, dtype=jnp.int32)

    def threshold_bit(i, t):
        cand = t | jnp.left_shift(jnp.uint32(1), (31 - i).astype(jnp.uint32))
        return jnp.where(count(key >= cand[..., None]) >= k, cand, t)

    t = jax.lax.fori_loop(0, 32, threshold_bit,
                          jnp.zeros(key.shape[:-1], jnp.uint32))
    above = count(key > t[..., None])
    tie = key == t[..., None]
    need = k - above
    blocks = jnp.arange(key.shape[-1], dtype=jnp.int32)
    index_bits = threshold_passes(key.shape[-1]) - 32

    def index_bit(i, last):
        # the largest bound with fewer than ``need`` ties below it: the
        # index of the tie that makes ``k``
        cand = last | jnp.left_shift(1, index_bits - 1 - i)
        fewer = count(tie & (blocks < cand[..., None])) < need
        return jnp.where(fewer, cand, last)

    last = jax.lax.fori_loop(0, index_bits, index_bit,
                             jnp.zeros(key.shape[:-1], jnp.int32))
    mask = (key > t[..., None]) | (tie & (blocks <= last[..., None]))
    # t == 0: fewer than k keys above -inf, and the ties are the -infs
    return mask & (key > 0), (t > 0) & (above + count(tie) > k)


def block_scores(q, k, config: SparseConfig):
    """``[chunks, B, KV, Q, blocks]`` float32: each query's block scores
    for its K/V head, ``select_chunk`` queries a chunk (the last padded
    past ``T``): ``+inf`` for the forced causal blocks (init and local),
    ``-inf`` after the query's own block.  ``q`` [B, T, H, D], ``k`` [B,
    T, KV, D]; products in ``q``'s type, float32 out, softmax in
    float32."""
    c = config
    b, t, h, d = q.shape
    kv = k.shape[2]
    group = h // kv
    nblocks = -(-t // c.block_size)
    kc = compress_keys(k, c.kernel_size, c.kernel_stride).astype(q.dtype)
    windows = kc.shape[1]
    ratio = c.block_size // c.kernel_stride
    lo = c.kernel_size // c.kernel_stride - 1  # windows before a block's first
    chunk = min(c.select_chunk, t)
    n_chunks = -(-t // chunk)
    pad = n_chunks * chunk - t
    qs = jnp.pad(q, [(0, 0), (0, pad), (0, 0), (0, 0)]).reshape(
        b, n_chunks, chunk, kv, group, d
    ).transpose(1, 0, 3, 2, 4, 5)  # [chunks, B, KV, Q, group, D]
    scale = d ** -0.5

    def one(args):
        qc, start = args
        pos = start + jnp.arange(chunk)  # [Q]
        s = jnp.einsum(
            "bgqhd,bjgd->bgqhj", qc, kc, preferred_element_type=jnp.float32
        ) * scale
        # window j holds tokens [stride j, stride j + kernel)
        ends = jnp.arange(windows) * c.kernel_stride + c.kernel_size - 1
        valid = ends[None, :] <= pos[:, None]  # [Q, windows]
        s = jnp.where(valid[None, None, :, None], s, -jnp.inf)
        m = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0))
        total = jnp.sum(e, axis=-1, keepdims=True)
        p = jnp.sum(e / jnp.where(total > 0, total, 1.0), axis=3)  # [B,KV,Q,j]
        # block m: the max over windows ratio m - lo .. ratio m + ratio - 1
        padded = jnp.pad(p, [(0, 0), (0, 0), (0, 0),
                             (lo, nblocks * ratio - windows)])
        score = functools.reduce(jnp.maximum, [
            padded[..., o: o + nblocks * ratio: ratio]
            for o in range(lo + ratio)
        ])  # [B, KV, Q, nblocks]
        blocks = jnp.arange(nblocks)
        own = pos[:, None] // c.block_size
        first_local = jnp.maximum(pos - c.window_size + 1, 0)[:, None] // (
            c.block_size
        )
        causal = blocks[None, :] <= own
        forced = (blocks[None, :] < c.init_blocks) | (blocks[None, :] >= first_local)
        score = jnp.where(forced & causal, jnp.inf, score)
        return jnp.where(causal, score, -jnp.inf)

    starts = jnp.arange(n_chunks, dtype=jnp.int32) * chunk
    return jax.lax.map(one, (qs, starts))


def select_mask(q, k, config: SparseConfig):
    """``(mask, tied)``: ``mask`` [B, KV, T, blocks] bool, each query's
    selected blocks for its K/V head (the forced ones, init and local,
    and the highest-scoring causal blocks left, ``topk`` in all, ties to
    the lower block; every causal block where fewer exist); ``tied`` [B,
    KV, T] as :func:`top_mask` gives it.  Arguments as
    :func:`block_scores` takes them."""
    score = block_scores(q, k, config)  # [chunks, B, KV, Q, blocks]
    mask, tied = top_mask(score, min(config.topk, score.shape[-1]))
    b, kv, t = k.shape[0], k.shape[2], q.shape[1]
    rows = lambda x: x.transpose(1, 2, 0, 3, *range(4, x.ndim)).reshape(
        b, kv, -1, *x.shape[4:])[:, :, :t]
    return rows(mask), rows(tied)


def as_mask(sel, t: int, block: int):
    """A selection as a mask ``[B, KV, T, blocks]`` bool: ``sel`` itself
    where it is one, else from block indices ``[B, KV, T, topk]`` (-1
    for none)."""
    if sel.dtype == jnp.bool_:
        return sel
    blocks = jnp.arange(-(-t // block), dtype=sel.dtype)
    return jnp.any(sel[..., None] == blocks, axis=-2)


def mask_indices(mask, k: int):
    """``[B, KV, T, k]`` int32: the blocks of ``mask`` in ascending
    order, -1 where fewer exist (no sort: slot ``s`` holds the count of
    blocks before the one that makes ``s + 1``)."""
    n = mask.shape[-1]
    made = jnp.cumsum(mask, axis=-1, dtype=jnp.int32)  # [.., N]
    at = jnp.sum(made[..., None, :] <= jnp.arange(k, dtype=jnp.int32)[:, None],
                 axis=-1, dtype=jnp.int32)
    return jnp.where(at < n, at, -1)


def select_blocks(q, k, config: SparseConfig):
    """``[B, KV, T, topk]`` int32: each query's selected blocks for its
    K/V head (:func:`select_mask`) in ascending order, -1 where fewer
    causal blocks exist."""
    mask, _ = select_mask(q, k, config)
    return mask_indices(mask, min(config.topk, mask.shape[-1]))


def visit_stats(sel, block: int):
    """``(keys, blocks)`` float32: the mean over (batch, K/V head,
    query) of the causal keys a query visits and of the blocks it
    selected; ``sel`` a mask or block indices (:func:`as_mask`)."""
    t = sel.shape[2]
    mask = as_mask(sel, t, block)
    pos = jnp.arange(t)[None, None, :, None]
    starts = jnp.arange(mask.shape[-1]) * block
    keys = jnp.clip(pos - starts + 1, 0, block)
    return (jnp.mean(jnp.sum(jnp.where(mask, keys, 0), -1).astype(jnp.float32)),
            jnp.mean(jnp.sum(mask, -1, dtype=jnp.int32).astype(jnp.float32)))


def block_words(sel, t: int, config: SparseConfig):
    """``[B, KV, T, key tiles]`` float32: bit ``b`` of a token's word for
    a key tile says it selected block ``b`` of that tile (at most
    ``_BITS`` blocks a tile, so that float32 holds a word whole); ``sel``
    a mask or block indices (:func:`as_mask`)."""
    tile = config.tile_for(t)
    per_tile = tile // config.block_size
    mask = as_mask(sel, t, config.block_size)
    tiles = mask.reshape(*mask.shape[:-1], t // tile, per_tile)
    bit = jnp.left_shift(1, jnp.arange(per_tile, dtype=jnp.int32))
    return jnp.sum(jnp.where(tiles, bit, 0), axis=-1).astype(jnp.float32)


def causal_pairs(n_tiles: int) -> int:
    """(query tile, key tile) pairs with a visible pair of positions:
    what a visit list may hold at most."""
    return n_tiles * (n_tiles + 1) // 2


def visit_lists(words):
    """``(query tiles, key tiles, count)`` in the forward order and
    ``(query tiles, key tiles)`` in the dK/dV order, int32, ``[B*KV,
    causal_pairs]`` and ``[B*KV]``: the (query tile, key tile) pairs
    where some token of the query tile selected a block of the key tile,
    by query tile then key tile (and by key tile then query tile), each
    list padded with its last pair."""
    b, kv, t, n = words.shape
    tile = t // n
    union = (words.reshape(b * kv, n, tile, n) != 0).any(axis=2)  # [BKV, i, j]
    steps = causal_pairs(n)
    count = union.sum(axis=(1, 2)).astype(jnp.int32)

    def listed(flat):
        order = jnp.argsort(~flat, axis=1, stable=True)[:, :steps]
        at = jnp.minimum(jnp.arange(steps)[None], count[:, None] - 1)
        return jnp.take_along_axis(order, at, axis=1).astype(jnp.int32)

    fwd = listed(union.reshape(b * kv, n * n))
    bwd = listed(union.transpose(0, 2, 1).reshape(b * kv, n * n))
    return (fwd // n, fwd % n, count), (bwd % n, bwd // n)


# -- the kernels ----------------------------------------------------------


class _Plan(NamedTuple):
    scale: float
    group: int  # query heads a K/V head serves
    tile: int
    block: int
    steps: int  # the lists' length: the grids' pair dimension
    interpret: bool


def _word_bits(w_ref, j, plan):
    """``[tile, tile]`` bool: key column ``c`` is in a block the row's
    token selected, for key tile ``j``."""
    words = w_ref[0]  # [tile, key tiles] float32
    lane = jax.lax.broadcasted_iota(jnp.int32, words.shape, 1)
    w = jnp.max(jnp.where(lane == j, words, 0.0), axis=1, keepdims=True)
    w = jnp.broadcast_to(w.astype(jnp.int32), (plan.tile, plan.tile))
    shift = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1) // plan.block
    return jnp.bitwise_and(jax.lax.shift_right_logical(w, shift), 1) == 1


def _visible(w_ref, i, j, plan):
    """The selection's mask and causality, for query tile ``i`` and key
    tile ``j``."""
    shape = (plan.tile, plan.tile)
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + i * plan.tile
    cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1) + j * plan.tile
    return _word_bits(w_ref, j, plan) & (rows >= cols)


def _run_edges(order, n, s, steps):
    """``(live, first, last)`` of step ``s`` in a list whose runs are
    keyed by ``order`` (a scalar a step): ``live`` below the count; a
    run's first and last step."""
    here = order(s)
    prev = order(jnp.maximum(s - 1, 0))
    nxt = order(jnp.minimum(s + 1, steps - 1))
    live = s < n
    return (live, live & ((s == 0) | (prev != here)),
            live & ((s == n - 1) | (nxt != here)))


def _scores(q_ref, k_ref, w_ref, i, j, plan):
    q, k = q_ref[0], k_ref[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * plan.scale
    return q, k, s, _visible(w_ref, i, j, plan)


def _fwd_kernel(fq, fk, count, q_ref, k_ref, v_ref, w_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, plan):
    bh, s = pl.program_id(0), pl.program_id(1)
    g = jax.lax.div(bh, plan.group)
    i, j = fq[g, s], fk[g, s]
    live, first, last = _run_edges(lambda x: fq[g, x], count[g], s, plan.steps)

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _flash.NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(live)
    def _compute():
        _, _, s_, visible = _scores(q_ref, k_ref, w_ref, i, j, plan)
        v = v_ref[0]
        s_ = jnp.where(visible, s_, _flash.NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_cur = jnp.maximum(jnp.max(s_, axis=1, keepdims=True), m_prev)
        # rows with nothing visible yet keep NEG_INF: exp(NEG_INF - 0) = 0
        m_safe = jnp.where(m_cur <= _flash.NEG_INF / 2, 0.0, m_cur)
        m_from = jnp.where(m_prev <= _flash.NEG_INF / 2, _flash.NEG_INF, m_prev)
        p = jnp.exp(s_ - _flash._across(m_safe, plan.tile))
        correction = jnp.exp(m_from - m_safe)
        l_ref[...] = l_prev * correction + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * _flash._across(
            correction, acc_ref.shape[1]
        ) + pv
        m_ref[...] = m_cur

    @pl.when(last)
    def _finalize():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / _flash._across(
            jnp.where(l == 0.0, 1.0, l), acc_ref.shape[1]
        )).astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(jnp.maximum(l, 1e-37))


def _backward_tile(q_ref, k_ref, v_ref, w_ref, do_ref, lse_ref, delta_ref,
                   i, j, plan):
    q, k, s, visible = _scores(q_ref, k_ref, w_ref, i, j, plan)
    do = do_ref[0]
    p = jnp.exp(s - _flash._across(lse_ref[0], plan.tile))
    p = jnp.where(visible, p, 0.0)
    dp = jax.lax.dot_general(
        do, v_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - _flash._across(delta_ref[0], plan.tile))
    return q, k, do, p, ds


def _dq_kernel(fq, fk, count, q_ref, k_ref, v_ref, w_ref, do_ref, lse_ref,
               delta_ref, dq_ref, acc_ref, *, plan):
    bh, s = pl.program_id(0), pl.program_id(1)
    g = jax.lax.div(bh, plan.group)
    i, j = fq[g, s], fk[g, s]
    live, first, last = _run_edges(lambda x: fq[g, x], count[g], s, plan.steps)

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _compute():
        _, k, _, _, ds = _backward_tile(
            q_ref, k_ref, v_ref, w_ref, do_ref, lse_ref, delta_ref, i, j, plan
        )
        acc_ref[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(last)
    def _finalize():
        dq_ref[0] = (acc_ref[...] * plan.scale).astype(dq_ref.dtype)


def _dkv_kernel(tq, tk, count, q_ref, k_ref, v_ref, w_ref, do_ref, lse_ref,
                delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, plan):
    g, s, h = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    i, j = tq[g, s], tk[g, s]
    live, first, last = _run_edges(lambda x: tk[g, x], count[g], s, plan.steps)

    @pl.when(first & (h == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(live)
    def _compute():
        q, _, do, p, ds = _backward_tile(
            q_ref, k_ref, v_ref, w_ref, do_ref, lse_ref, delta_ref, i, j, plan
        )
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(last & (h == plan.group - 1))
    def _finalize():
        dk_ref[0] = (dk_acc[...] * plan.scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _call(kernel, plan, grid, in_specs, out_specs, out_shape, scratch):
    extra = dict(interpret=True) if plan.interpret else dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid),
        )
    )
    return pl.pallas_call(
        functools.partial(kernel, plan=plan),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        **extra,
    )


def _of_query(plan, width):
    """A query-side block [1, tile, width] for the (query head, pair)
    grids, at the pair's query tile."""
    return pl.BlockSpec(
        (1, plan.tile, width),
        lambda b, s, fq, fk, n: (b, fq[jax.lax.div(b, plan.group), s], 0),
    )


def _of_key(plan, width):
    return pl.BlockSpec(
        (1, plan.tile, width),
        lambda b, s, fq, fk, n: (
            jax.lax.div(b, plan.group), fk[jax.lax.div(b, plan.group), s], 0
        ),
    )


def _words_of_query(plan, n_tiles):
    return pl.BlockSpec(
        (1, plan.tile, n_tiles),
        lambda b, s, fq, fk, n: (
            jax.lax.div(b, plan.group), fq[jax.lax.div(b, plan.group), s], 0
        ),
    )


@functools.partial(jax.jit, static_argnames="plan")
def _forward(q, k, v, words, fq, fk, count, *, plan):
    """``o`` [BH, T, D] and ``lse`` [BH, T] float32 of ``q`` [BH, T, D],
    ``k``, ``v`` [B*KV, T, D] and ``words`` [B*KV, T, key tiles]."""
    bh, t, d = q.shape
    n_tiles = words.shape[-1]
    with jax.named_scope("sparse.fwd"):
        o, lse = _call(
            _fwd_kernel, plan, (bh, plan.steps),
            [_of_query(plan, d), _of_key(plan, d), _of_key(plan, v.shape[-1]),
             _words_of_query(plan, n_tiles)],
            [_of_query(plan, v.shape[-1]), _of_query(plan, 128)],
            [jax.ShapeDtypeStruct((bh, t, v.shape[-1]), q.dtype),
             jax.ShapeDtypeStruct((bh, t, 128), jnp.float32)],
            [pltpu.VMEM((plan.tile, v.shape[-1]), jnp.float32),
             pltpu.VMEM((plan.tile, 128), jnp.float32),
             pltpu.VMEM((plan.tile, 128), jnp.float32)],
        )(fq, fk, count, q, k, v, words)
    return o, lse[..., 0]


@functools.partial(jax.jit, static_argnames="plan")
def _backward(q, k, v, words, fwd, bwd, o, lse, do, *, plan):
    """``(dq, dk, dv)`` in their inputs' shapes."""
    fq, fk, count = fwd
    tq, tk = bwd
    bh, t, d = q.shape
    bkv, dv_width = k.shape[0], v.shape[-1]
    n_tiles = words.shape[-1]
    lse_b, delta_b = _flash._lse_delta_lanes(o, lse, do)
    with jax.named_scope("sparse.dq"):
        dq = _call(
            _dq_kernel, plan, (bh, plan.steps),
            [_of_query(plan, d), _of_key(plan, d), _of_key(plan, dv_width),
             _words_of_query(plan, n_tiles), _of_query(plan, dv_width),
             _of_query(plan, 128), _of_query(plan, 128)],
            _of_query(plan, d),
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            [pltpu.VMEM((plan.tile, d), jnp.float32)],
        )(fq, fk, count, q, k, v, words, do, lse_b, delta_b)

    # (K/V head, pair in key-tile order, query head of the group)
    def of_q(width):
        return pl.BlockSpec(
            (1, plan.tile, width),
            lambda g, s, h, tq, tk, n: (g * plan.group + h, tq[g, s], 0),
        )

    def of_k(width):
        return pl.BlockSpec(
            (1, plan.tile, width), lambda g, s, h, tq, tk, n: (g, tk[g, s], 0)
        )

    words_spec = pl.BlockSpec(
        (1, plan.tile, n_tiles), lambda g, s, h, tq, tk, n: (g, tq[g, s], 0)
    )
    with jax.named_scope("sparse.dkv"):
        dk, dv = _call(
            _dkv_kernel, plan, (bkv, plan.steps, plan.group),
            [of_q(d), of_k(d), of_k(dv_width), words_spec, of_q(dv_width),
             of_q(128), of_q(128)],
            [of_k(d), of_k(dv_width)],
            [jax.ShapeDtypeStruct(k.shape, k.dtype),
             jax.ShapeDtypeStruct(v.shape, v.dtype)],
            [pltpu.VMEM((plan.tile, d), jnp.float32),
             pltpu.VMEM((plan.tile, dv_width), jnp.float32)],
        )(tq, tk, count, q, k, v, words, do, lse_b, delta_b)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _sparse(q, k, v, words, fwd, bwd, plan):
    return _forward(q, k, v, words, *fwd, plan=plan)[0]


def _sparse_fwd(q, k, v, words, fwd, bwd, plan):
    o, lse = _forward(q, k, v, words, *fwd, plan=plan)
    o = checkpoint_name(o, _flash.RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse, _flash.RESIDUAL_NAMES[1])
    return o, (q, k, v, words, fwd, bwd, o, lse)


def _sparse_bwd(plan, residuals, do):
    q, k, v, words, fwd, bwd, o, lse = residuals
    # The rule is traced outside the caller's scope: name it again, so
    # that the backward kernels' device time is the layer's.
    with jax.named_scope("attn.sparse"):
        dq, dk, dv = _backward(q, k, v, words, fwd, bwd, o, lse, do, plan=plan)
    return dq, dk, dv, None, None, None


_sparse.defvjp(_sparse_fwd, _sparse_bwd)


def selection_bytes(batch: int, t: int, kv: int, config: SparseConfig) -> int:
    """Bytes of :func:`selection_arrays`: the words, and four visit
    lists and a count a K/V head."""
    n = t // config.tile_for(t)
    return batch * kv * (t * n + 4 * causal_pairs(n) + 1) * 4


def selection_arrays(sel, t: int, config: SparseConfig):
    """``(words, forward lists, dK/dV lists)`` the kernels read, from a
    selection (a mask or block indices, :func:`as_mask`); each under
    :data:`SELECTION_NAME`."""
    words = block_words(sel, t, config)
    fwd, bwd = visit_lists(words)
    b, kv = sel.shape[:2]
    name = lambda x: checkpoint_name(x, SELECTION_NAME)
    return (name(words.reshape(b * kv, t, -1)),
            jax.tree_util.tree_map(name, fwd), jax.tree_util.tree_map(name, bwd))


def sparse_attention(q, k, v, selection, config: SparseConfig, *,
                     sm_scale=None, interpret=None):
    """``[B, T, H, Dv]``: each query over the keys of its K/V head's
    selected blocks that are not after it.  ``q`` [B, T, H, D], ``k``
    [B, T, KV, D], ``v`` [B, T, KV, Dv]; ``selection`` is what
    :func:`selection_arrays` makes of :func:`select_mask`'s choice, in
    which every query holds its own block (the local window's): a query
    tile is then always paired with itself, so each tile's output and row
    statistics are written and no query is left with nothing to attend.
    ``T`` whole tiles (:meth:`SparseConfig.tile_for`)."""
    b, t, h, d = q.shape
    kv = k.shape[2]
    if interpret is None:
        interpret = _flash._interpret_default()
    tile = config.tile_for(t)
    if not interpret and tile % 128:
        raise ValueError(f"a compiled tile is a multiple of 128, not {tile}")
    words, fwd, bwd = selection
    plan = _Plan(
        float(d ** -0.5 if sm_scale is None else sm_scale), h // kv,
        tile, config.block_size, causal_pairs(t // tile), bool(interpret),
    )
    if telemetry.armed():
        # One record a call traced while the recorder is armed: what the
        # kernels of this call may walk, from its static arguments.
        telemetry.emit("attn.sparse", detail=dict(
            batch=b, tokens=t, heads=h, kv_heads=kv, head_dim=d,
            v_width=v.shape[-1], tile=tile, block=config.block_size,
            topk=config.topk, grid_pairs=plan.steps,
            # what a checkpoint that keeps the residuals keeps a call
            residual_bytes=b * t * h * v.shape[-1] * q.dtype.itemsize
            + b * h * t * 4 + int(np.prod(words.shape)) * 4,
        ))
    to_bht = _flash._bthd_to_bht
    o = _sparse(to_bht(q), to_bht(k), to_bht(v), words, fwd, bwd, plan)
    return _flash._bht_to_bthd(o, b, h)

