"""Ring attention: sequence parallelism over the ``sp`` mesh axis.

Long-context attention where the sequence is sharded across devices and
K/V shards rotate around the ring via ``lax.ppermute`` while each device
accumulates its queries' attention with the online-softmax recurrence
(:func:`rayfed_tpu.ops.attention.blockwise_accumulate`).  Per step the
ppermute overlaps ICI transfer of the *next* K/V block with compute on the
current one — XLA schedules the collective-permute asynchronously, which
is the whole point of the ring formulation (Liu et al., Ring Attention
with Blockwise Transformers, 2023).

Absent from the reference by design (SURVEY §5.7: "no ring attention,
context parallel, blockwise, or Ulysses anywhere") — here it is a
party-local sharding strategy of the compute layer.

Two inner-step implementations:

- ``blockwise`` — the XLA online-softmax recurrence
  (:func:`rayfed_tpu.ops.attention.blockwise_accumulate`); runs anywhere.
- ``flash`` (:func:`ring_flash_attention`) — each ring step runs the
  Pallas flash kernel on the resident K/V block and the per-step
  (o, lse) partials merge by log-sum-exp; backward rings the K/V blocks
  a second time, accumulating dK/dV *onto the rotating buffers* so each
  block arrives home carrying its full gradient.  This is the TPU path:
  the MXU sees the same tiled kernel as single-device flash attention.

Entry points:

- :func:`ring_attention` / :func:`ring_flash_attention` — collective
  forms, call *inside* ``shard_map`` with sequence-sharded
  [B, T_local, H, D] blocks.
- :func:`make_ring_attention` — wraps either in ``shard_map`` over a
  mesh axis; takes/returns global [B, T, H, D] arrays.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from rayfed_tpu.ops.attention import (
    as_attn_fn,
    blockwise_accumulate,
    blockwise_finalize,
    init_blockwise_state,
)
from rayfed_tpu.ops.flash_attention import (
    NEG_INF,
    _bht_to_bthd,
    _bthd_to_bht,
    _fit_block,
    _flash_backward_pallas,
    _flash_forward,
    _interpret_default,
    _lse_delta_lanes,
)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    causal: bool = False,
    sm_scale: Optional[float] = None,
) -> jax.Array:
    """Collective ring attention over ``axis_name`` (call inside shard_map).

    ``q``/``k``/``v``: this device's sequence shard, [B, T_local, H, D].
    Shard *i* holds global positions ``[i*T_local, (i+1)*T_local)``.
    Returns the attention output for the local queries, same shape/dtype.
    """
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    t_local = q.shape[1]
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    # Rotate kv "forward" (device d hands its block to d+1), so at step i
    # device d holds the kv block originally owned by (d - i) mod n.
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
    q_offset = my_idx * t_local

    def body(carry, step):
        o, m, l, k_cur, v_cur = carry
        src = jnp.mod(my_idx - step, axis_size)
        o, m, l = blockwise_accumulate(
            q,
            k_cur,
            v_cur,
            o,
            m,
            l,
            scale=scale,
            q_offset=q_offset,
            kv_offset=src * t_local,
            causal=causal,
        )
        k_cur = lax.ppermute(k_cur, axis_name, perm)
        v_cur = lax.ppermute(v_cur, axis_name, perm)
        return (o, m, l, k_cur, v_cur), None

    state = init_blockwise_state(q) + (k, v)
    (o, _m, l, _k, _v), _ = lax.scan(body, state, jnp.arange(axis_size))
    return blockwise_finalize(o, l, q.dtype)


# ---------------------------------------------------------------------------
# Flash-inner ring: pallas kernels per step, lse-merge across steps
# ---------------------------------------------------------------------------


def _merge_partial(o_acc, lse_acc, o_i, lse_i):
    """Log-sum-exp merge of two *normalized* partial attention results.

    ``o_acc`` f32 [BH, T, D] with normalizer ``lse_acc`` [BH, T]; fully
    absent partials carry ``lse == NEG_INF`` and contribute nothing.
    """
    m = jnp.maximum(lse_acc, lse_i)
    m_safe = jnp.where(m <= NEG_INF / 2, 0.0, m)
    w_acc = jnp.exp(jnp.where(lse_acc <= NEG_INF / 2, NEG_INF, lse_acc) - m_safe)
    w_i = jnp.exp(jnp.where(lse_i <= NEG_INF / 2, NEG_INF, lse_i) - m_safe)
    denom = w_acc + w_i
    denom_safe = jnp.where(denom == 0.0, 1.0, denom)
    o = (
        o_acc * (w_acc / denom_safe)[..., None]
        + o_i.astype(jnp.float32) * (w_i / denom_safe)[..., None]
    )
    lse = m + jnp.log(denom_safe)
    return o, lse


def _ring_flash_fwd_inner(
    q, k, v, axis_name, causal, scale, block_q, block_k, interpret
):
    """[BH, T, D] ring forward → (o f32, lse f32)."""
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
    flash = functools.partial(
        _flash_forward,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        q_offset=0,
        kv_offset=0,
        interpret=interpret,
        # f32 partials: rounding each step's o to bf16 before the merge
        # would accumulate error with ring size; round once at the end.
        out_dtype=jnp.float32,
    )

    # Step 0 is every device's own (diagonal) block — the only one that
    # needs in-kernel causal masking, so it runs unrolled.  Later blocks
    # are either entirely visible (owner before me in the ring) or
    # entirely masked; visibility is applied to the partial's lse, so
    # one causal=False kernel instance serves every scanned step.
    o_acc, lse_0 = flash(q, k, v, causal=causal)

    def body(carry, step):
        o_acc, lse_acc, k_cur, v_cur = carry
        k_cur = lax.ppermute(k_cur, axis_name, perm)
        v_cur = lax.ppermute(v_cur, axis_name, perm)
        o_i, lse_i = flash(q, k_cur, v_cur, causal=False)
        if causal:
            src = jnp.mod(my_idx - step, axis_size)
            lse_i = jnp.where(src < my_idx, lse_i, NEG_INF)
        o_acc, lse_acc = _merge_partial(o_acc, lse_acc, o_i, lse_i)
        return (o_acc, lse_acc, k_cur, v_cur), None

    (o_acc, lse_acc, _, _), _ = lax.scan(
        body, (o_acc, lse_0, k, v), jnp.arange(1, axis_size)
    )
    return o_acc, lse_acc


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _ring_flash_bht(
    q, k, v, axis_name, causal, scale, block_q, block_k, interpret
):
    out, _ = _ring_flash_fwd(
        q, k, v, axis_name, causal, scale, block_q, block_k, interpret
    )
    return out


def _ring_flash_fwd(
    q, k, v, axis_name, causal, scale, block_q, block_k, interpret
):
    o_acc, lse = _ring_flash_fwd_inner(
        q, k, v, axis_name, causal, scale, block_q, block_k, interpret
    )
    out = o_acc.astype(q.dtype)
    return out, (q, k, v, out, lse)


def _ring_flash_bwd(
    axis_name, causal, scale, block_q, block_k, interpret, res, do
):
    """Backward ring: K/V make a second full loop, dK/dV ride along.

    Each step runs the standard flash backward kernels (dQ and dK/dV)
    against the resident K/V block using the *final* lse/delta — the
    global-softmax weights — and the dK/dV partials accumulate onto
    buffers that rotate with their block; after ``axis_size`` rotations
    every block (and its gradient) is back on its owner.
    """
    q, k, v, out, lse = res
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
    # lse/delta lane-broadcasts depend only on (out, lse, do): hoist them
    # out of the ring loop instead of recomputing per step.
    lse_delta_b = _lse_delta_lanes(out, lse, do)
    bwd = functools.partial(
        _flash_backward_pallas,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        q_offset=0,
        kv_offset=0,
        interpret=interpret,
        lse_delta_b=lse_delta_b,
        # f32 partials — see the forward's out_dtype note.
        out_dtype=jnp.float32,
    )

    # Step 0: the diagonal block, in-kernel causal mask (see fwd).
    dq_0, dk_0, dv_0 = bwd(q, k, v, out, lse, do, causal=causal)

    def body(carry, step):
        dq_acc, k_cur, v_cur, dk_cur, dv_cur = carry
        # Rotate gradients WITH their block so each block accumulates
        # its contributions as it tours the ring.
        k_cur = lax.ppermute(k_cur, axis_name, perm)
        v_cur = lax.ppermute(v_cur, axis_name, perm)
        dk_cur = lax.ppermute(dk_cur, axis_name, perm)
        dv_cur = lax.ppermute(dv_cur, axis_name, perm)
        dq_i, dk_i, dv_i = bwd(q, k_cur, v_cur, out, lse, do, causal=False)
        if causal:
            # jnp.where, not a multiply: an invisible block's kernel
            # output is exp(s - lse) of scores the softmax never saw —
            # potentially inf, and inf·0 would poison the sum with NaN.
            src = jnp.mod(my_idx - step, axis_size)
            visible = src < my_idx
            dq_i = jnp.where(visible, dq_i, 0)
            dk_i = jnp.where(visible, dk_i, 0)
            dv_i = jnp.where(visible, dv_i, 0)
        dq_acc = dq_acc + dq_i.astype(jnp.float32)
        dk_cur = dk_cur + dk_i.astype(jnp.float32)
        dv_cur = dv_cur + dv_i.astype(jnp.float32)
        return (dq_acc, k_cur, v_cur, dk_cur, dv_cur), None

    carry0 = (
        dq_0.astype(jnp.float32),
        k,
        v,
        dk_0.astype(jnp.float32),
        dv_0.astype(jnp.float32),
    )
    (dq_acc, _, _, dk_cur, dv_cur), _ = lax.scan(
        body, carry0, jnp.arange(1, axis_size)
    )
    # One final hop delivers each block's accumulated gradient home.
    dk_cur = lax.ppermute(dk_cur, axis_name, perm)
    dv_cur = lax.ppermute(dv_cur, axis_name, perm)
    return (
        dq_acc.astype(q.dtype),
        dk_cur.astype(k.dtype),
        dv_cur.astype(v.dtype),
    )


_ring_flash_bht.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    block_q: int = 1024,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Ring attention with the Pallas flash kernel as the inner step.

    Same contract as :func:`ring_attention` (call inside ``shard_map``
    with [B, T_local, H, D] sequence shards; shard *i* holds global
    positions ``[i·T_local, (i+1)·T_local)``) — but each step's block
    attention runs the tiled MXU kernel and the per-step results merge
    by log-sum-exp, so per-block throughput matches single-device
    :func:`rayfed_tpu.ops.flash_attention.flash_attention`.
    """
    if interpret is None:
        interpret = _interpret_default()
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    t_local = q.shape[1]
    block_q = _fit_block(t_local, block_q)
    block_k = _fit_block(k.shape[1], block_k)
    qh, kh, vh = _bthd_to_bht(q), _bthd_to_bht(k), _bthd_to_bht(v)
    oh = _ring_flash_bht(
        qh, kh, vh, axis_name, causal, scale, block_q, block_k, interpret
    )
    return _bht_to_bthd(oh, q.shape[0], q.shape[2])


# ---------------------------------------------------------------------------
# Zigzag layout: load-balanced causal ring
# ---------------------------------------------------------------------------
#
# A contiguous causal ring wastes compute: at step s device d's resident
# K/V block is fully masked whenever its owner sits *after* d, so about
# half of all (device, step) kernels contribute nothing (they still run —
# ppermute keeps the devices in lockstep).  The zigzag layout (T split
# into 2n chunks; device d holds chunks (d, 2n−1−d)) balances the causal
# triangle instead:
#
#   - (q_lo, kv_hi): the peer's high chunk is always in q_lo's future —
#     statically skipped, no kernel at all;
#   - (q_hi, kv_lo): the peer's low chunk is always in q_hi's past —
#     statically a full (unmasked) kernel;
#   - (q_lo, kv_lo) is visible iff src < my and (q_hi, kv_hi) iff
#     src > my — exactly one per step, so ONE kernel on operands
#     selected by that predicate covers both.
#
# Per step every device runs exactly two half-chunk kernels of useful
# work; total causal FLOPs match the T²/2 triangle with no waste — 2×
# the effective throughput of the contiguous causal ring.


def _zigzag_flash_fwd_inner(q, k, v, axis_name, scale, block_q, block_k, interpret):
    """[BH, 2·Tc, D] zigzag forward → (o f32, lse f32), halves stacked."""
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
    tc = q.shape[1] // 2
    flash = functools.partial(
        _flash_forward,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        q_offset=0,
        kv_offset=0,
        interpret=interpret,
        out_dtype=jnp.float32,
    )
    q_lo, q_hi = q[:, :tc], q[:, tc:]

    # Step 0: both within-chunk diagonals (causal kernels) plus the
    # always-full (q_hi, kv_lo) block.
    o_lo, lse_lo = flash(q_lo, k[:, :tc], v[:, :tc], causal=True)
    o_hi, lse_hi = flash(q_hi, k[:, tc:], v[:, tc:], causal=True)
    o_f, lse_f = flash(q_hi, k[:, :tc], v[:, :tc], causal=False)
    o_hi, lse_hi = _merge_partial(o_hi, lse_hi, o_f, lse_f)

    def body(carry, step):
        o_lo, lse_lo, o_hi, lse_hi, k_cur, v_cur = carry
        k_cur = lax.ppermute(k_cur, axis_name, perm)
        v_cur = lax.ppermute(v_cur, axis_name, perm)
        src = jnp.mod(my_idx - step, axis_size)
        k_lo, k_hi = k_cur[:, :tc], k_cur[:, tc:]
        v_lo, v_hi = v_cur[:, :tc], v_cur[:, tc:]

        # Static full block: the peer's low chunk is always visible to
        # my high chunk.
        o_f, lse_f = flash(q_hi, k_lo, v_lo, causal=False)
        o_hi, lse_hi = _merge_partial(o_hi, lse_hi, o_f, lse_f)

        # Gated block: exactly one of (q_lo, kv_lo) / (q_hi, kv_hi) is
        # visible; select the operands instead of computing both.
        pred = src < my_idx
        o_g, lse_g = flash(
            jnp.where(pred, q_lo, q_hi),
            jnp.where(pred, k_lo, k_hi),
            jnp.where(pred, v_lo, v_hi),
            causal=False,
        )
        m_lo = _merge_partial(o_lo, lse_lo, o_g, lse_g)
        m_hi = _merge_partial(o_hi, lse_hi, o_g, lse_g)
        o_lo = jnp.where(pred, m_lo[0], o_lo)
        lse_lo = jnp.where(pred, m_lo[1], lse_lo)
        o_hi = jnp.where(pred, o_hi, m_hi[0])
        lse_hi = jnp.where(pred, lse_hi, m_hi[1])
        return (o_lo, lse_lo, o_hi, lse_hi, k_cur, v_cur), None

    carry0 = (o_lo, lse_lo, o_hi, lse_hi, k, v)
    (o_lo, lse_lo, o_hi, lse_hi, _, _), _ = lax.scan(
        body, carry0, jnp.arange(1, axis_size)
    )
    return (
        jnp.concatenate([o_lo, o_hi], axis=1),
        jnp.concatenate([lse_lo, lse_hi], axis=1),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _zigzag_flash_bht(q, k, v, axis_name, scale, block_q, block_k, interpret):
    out, _ = _zigzag_flash_fwd(
        q, k, v, axis_name, scale, block_q, block_k, interpret
    )
    return out


def _zigzag_flash_fwd(q, k, v, axis_name, scale, block_q, block_k, interpret):
    o, lse = _zigzag_flash_fwd_inner(
        q, k, v, axis_name, scale, block_q, block_k, interpret
    )
    out = o.astype(q.dtype)
    return out, (q, k, v, out, lse)


def _zigzag_flash_bwd(
    axis_name, scale, block_q, block_k, interpret, res, do
):
    """Backward mirrors the forward's block schedule; dK/dV ride the ring."""
    q, k, v, out, lse = res
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
    tc = q.shape[1] // 2
    q_lo, q_hi = q[:, :tc], q[:, tc:]
    do_lo, do_hi = do[:, :tc], do[:, tc:]
    out_lo, out_hi = out[:, :tc], out[:, tc:]
    lse_lo, lse_hi = lse[:, :tc], lse[:, tc:]
    ld_lo = _lse_delta_lanes(out_lo, lse_lo, do_lo)
    ld_hi = _lse_delta_lanes(out_hi, lse_hi, do_hi)

    def bwd(qb, kb, vb, ob, lseb, dob, causal, ld):
        return _flash_backward_pallas(
            qb, kb, vb, ob, lseb, dob,
            scale=scale,
            causal=causal,
            block_q=block_q,
            block_k=block_k,
            q_offset=0,
            kv_offset=0,
            interpret=interpret,
            lse_delta_b=ld,
            out_dtype=jnp.float32,
        )

    # Step 0: the two diagonals + the static full block, all local.
    dq_lo, dk_lo, dv_lo = bwd(
        q_lo, k[:, :tc], v[:, :tc], out_lo, lse_lo, do_lo, True, ld_lo
    )
    dq_hi, dk_hi, dv_hi = bwd(
        q_hi, k[:, tc:], v[:, tc:], out_hi, lse_hi, do_hi, True, ld_hi
    )
    dq_f, dk_f, dv_f = bwd(
        q_hi, k[:, :tc], v[:, :tc], out_hi, lse_hi, do_hi, False, ld_hi
    )
    dq_hi = dq_hi + dq_f
    dk_lo = dk_lo + dk_f
    dv_lo = dv_lo + dv_f

    def body(carry, step):
        dq_lo, dq_hi, k_cur, v_cur, dk_cur, dv_cur = carry
        k_cur = lax.ppermute(k_cur, axis_name, perm)
        v_cur = lax.ppermute(v_cur, axis_name, perm)
        dk_cur = lax.ppermute(dk_cur, axis_name, perm)
        dv_cur = lax.ppermute(dv_cur, axis_name, perm)
        src = jnp.mod(my_idx - step, axis_size)
        k_l, k_h = k_cur[:, :tc], k_cur[:, tc:]
        v_l, v_h = v_cur[:, :tc], v_cur[:, tc:]

        # Static full block (q_hi, kv_lo of the resident pair).
        dq_f, dk_f, dv_f = bwd(
            q_hi, k_l, v_l, out_hi, lse_hi, do_hi, False, ld_hi
        )
        dq_hi = dq_hi + dq_f
        dk_cur = dk_cur.at[:, :tc].add(dk_f)
        dv_cur = dv_cur.at[:, :tc].add(dv_f)

        # Gated block on selected operands (see forward).
        pred = src < my_idx
        dq_g, dk_g, dv_g = bwd(
            jnp.where(pred, q_lo, q_hi),
            jnp.where(pred, k_l, k_h),
            jnp.where(pred, v_l, v_h),
            jnp.where(pred, out_lo, out_hi),
            jnp.where(pred, lse_lo, lse_hi),
            jnp.where(pred, do_lo, do_hi),
            False,
            tuple(jnp.where(pred, a, b) for a, b in zip(ld_lo, ld_hi)),
        )
        dq_lo = dq_lo + jnp.where(pred, dq_g, 0)
        dq_hi = dq_hi + jnp.where(pred, 0, dq_g)
        dk_cur = dk_cur.at[:, :tc].add(jnp.where(pred, dk_g, 0))
        dk_cur = dk_cur.at[:, tc:].add(jnp.where(pred, 0, dk_g))
        dv_cur = dv_cur.at[:, :tc].add(jnp.where(pred, dv_g, 0))
        dv_cur = dv_cur.at[:, tc:].add(jnp.where(pred, 0, dv_g))
        return (dq_lo, dq_hi, k_cur, v_cur, dk_cur, dv_cur), None

    carry0 = (
        dq_lo,
        dq_hi,
        k,
        v,
        jnp.concatenate([dk_lo, dk_hi], axis=1),
        jnp.concatenate([dv_lo, dv_hi], axis=1),
    )
    (dq_lo, dq_hi, _, _, dk_cur, dv_cur), _ = lax.scan(
        body, carry0, jnp.arange(1, axis_size)
    )
    # Final hop delivers each pair's accumulated gradient home.
    dk_cur = lax.ppermute(dk_cur, axis_name, perm)
    dv_cur = lax.ppermute(dv_cur, axis_name, perm)
    dq = jnp.concatenate([dq_lo, dq_hi], axis=1)
    return dq.astype(q.dtype), dk_cur.astype(k.dtype), dv_cur.astype(v.dtype)


_zigzag_flash_bht.defvjp(_zigzag_flash_fwd, _zigzag_flash_bwd)


def zigzag_ring_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    sm_scale: Optional[float] = None,
    block_q: int = 1024,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Collective zigzag causal ring attention (call inside shard_map).

    Shard layout: with 2n chunks of the global sequence, this device's
    [B, T_local, H, D] block is ``concat(chunk_d, chunk_{2n−1−d})`` —
    :func:`make_ring_attention` with ``layout="zigzag"`` applies the
    chunk permutation on global arrays.  Always causal (a non-causal
    ring has no imbalance to fix).
    """
    if interpret is None:
        interpret = _interpret_default()
    if q.shape[1] % 2:
        raise ValueError(
            f"zigzag shards hold a (low, high) chunk pair — T_local "
            f"({q.shape[1]}) must be even"
        )
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    tc = q.shape[1] // 2
    block_q = _fit_block(tc, block_q)
    block_k = _fit_block(tc, block_k)
    qh, kh, vh = _bthd_to_bht(q), _bthd_to_bht(k), _bthd_to_bht(v)
    oh = _zigzag_flash_bht(
        qh, kh, vh, axis_name, scale, block_q, block_k, interpret
    )
    return _bht_to_bthd(oh, q.shape[0], q.shape[2])


def _zigzag_perm(t: int, n_shards: int):
    """(perm, inv): chunk reorder so contiguous shard d = chunks
    (d, 2n−1−d) of the original sequence."""
    import numpy as np

    chunks = 2 * n_shards
    if t % chunks:
        raise ValueError(
            f"zigzag layout needs T ({t}) divisible by 2·axis_size "
            f"({chunks})"
        )
    tc = t // chunks
    order = []
    for d in range(n_shards):
        order.extend([d, chunks - 1 - d])
    idx = np.concatenate(
        [np.arange(c * tc, (c + 1) * tc) for c in order]
    )
    inv = np.argsort(idx)
    return idx, inv


def make_ring_attention(
    mesh: Mesh,
    seq_axis: str = "sp",
    *,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    use_flash: bool = False,
    block_q: int = 1024,
    block_k: int = 1024,
    layout: str = "contiguous",
):
    """Build a global-view ring attention fn sharded over ``mesh[seq_axis]``.

    Returned fn maps [B, T, H, D] → [B, T, H, D] with T sharded over
    ``seq_axis`` (T must divide evenly).  Batch stays replicated here;
    compose with dp by vmapping/sharding outside.  ``use_flash=True``
    runs the Pallas flash kernel per ring step (the TPU-fast path;
    interpreted on the CPU backend so the CPU test mesh exercises it too).

    ``layout="zigzag"`` (requires ``causal=True, use_flash=True``)
    balances the causal triangle across devices — each shard holds
    chunks (d, 2n−1−d) of the sequence, applied/undone here by a static
    chunk permutation — 2× the effective throughput of the contiguous
    causal ring (see the layout note above
    :func:`_zigzag_flash_fwd_inner`).
    """
    spec = P(None, seq_axis, None, None)
    if layout not in ("contiguous", "zigzag"):
        raise ValueError(f"unknown layout {layout!r}")
    if layout == "zigzag":
        if not (causal and use_flash):
            raise ValueError(
                "layout='zigzag' requires causal=True and use_flash=True "
                "(a non-causal ring has no imbalance to fix)"
            )
        n_shards = mesh.shape[seq_axis]
        sharded = jax.shard_map(
            functools.partial(
                zigzag_ring_flash_attention,
                axis_name=seq_axis,
                sm_scale=sm_scale,
                block_q=block_q,
                block_k=block_k,
            ),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )

        def apply_zigzag(qg, kg, vg):
            idx, inv = _zigzag_perm(qg.shape[1], n_shards)
            out = sharded(
                jnp.take(qg, idx, axis=1),
                jnp.take(kg, idx, axis=1),
                jnp.take(vg, idx, axis=1),
            )
            return jnp.take(out, inv, axis=1)

        return as_attn_fn(
            apply_zigzag, causal, sm_scale, "make_ring_attention"
        )
    if use_flash:
        fn = functools.partial(
            ring_flash_attention,
            axis_name=seq_axis,
            causal=causal,
            sm_scale=sm_scale,
            block_q=block_q,
            block_k=block_k,
        )
    else:
        fn = functools.partial(
            ring_attention, axis_name=seq_axis, causal=causal, sm_scale=sm_scale
        )
    sharded = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return as_attn_fn(sharded, causal, sm_scale, "make_ring_attention")
