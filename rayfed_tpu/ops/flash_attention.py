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

On the CPU backend (the test mode) the kernels run in pallas interpret
mode, so the CPU test mesh exercises the same code path; on any other
backend they are compiled — there is no silent interpreted run on a chip.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _interpret_default() -> bool:
    """Interpret mode is the CPU test mode only; every other backend
    compiles the kernel (and raises what the compiler raises)."""
    return jax.default_backend() == "cpu"


def _causal_dispatch(
    compute, causal, qi, ki, block_q, block_k, q_offset, kv_offset,
    window=None,
):
    """Run ``compute(masked)`` under the causal block classification.

    A block strictly past the diagonal contributes nothing (skipped); a
    block entirely at-or-before it needs no mask; only blocks straddling
    the diagonal pay for the iota/compare/select.  Shared by all three
    kernels so the boundary conditions cannot drift.

    ``window`` (sliding-window attention, requires ``causal``): query q
    sees keys in ``(q − window, q]``.  Blocks entirely below the band
    are skipped the same way fully-future blocks are — the kernel's
    FLOPs scale with O(T·window) instead of O(T²/2).
    """
    if not causal:
        compute(False)
        return
    q_first = q_offset + qi * block_q
    q_last = q_first + block_q - 1
    kv_first = kv_offset + ki * block_k
    kv_last = kv_first + block_k - 1
    active = kv_first <= q_last
    straddles = kv_last > q_first
    if window is not None:
        # Band-active: some pair satisfies q − k < window.
        active = active & (kv_last > q_first - window)
        # Band-straddling: the OLDEST pair falls outside the window.
        straddles = straddles | (q_last - kv_first >= window)

    @pl.when(active & jnp.logical_not(straddles))
    def _full():
        compute(False)

    @pl.when(active & straddles)
    def _diag():
        compute(True)


def _flash_fwd_kernel(
    q_ref,  # (1, block_q, d)
    k_ref,  # (1, block_k, d)
    v_ref,  # (1, block_k, d)
    o_ref,  # (1, block_q, d)
    lse_ref,  # (1, block_q, 128) — lane-broadcast so the block is tileable
    acc_ref,  # VMEM (block_q, d) f32
    m_ref,  # VMEM (block_q, 128) f32
    l_ref,  # VMEM (block_q, 128) f32
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    q_offset: int,
    kv_offset: int,
    window=None,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    num_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Under causality a kv block strictly after the last query row of this
    # q block contributes nothing — skip its matmuls entirely; a block
    # entirely at-or-before the diagonal needs no mask — skip the iota/
    # compare/select (only diagonal-straddling blocks pay for masking).
    # Offsets are static (compile-time) positions of the first q/kv token.
    def _compute(masked: bool):
        # Feed the MXU native-dtype (bf16) operands — casting to f32 first
        # would force f32 matmul passes at a fraction of bf16 throughput.
        # Accumulation is f32 via preferred_element_type.
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (block_q, block_k) f32
        if masked:
            q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = kv_offset + ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            visible = q_pos >= k_pos
            if window is not None:
                visible = visible & (q_pos - k_pos < window)
            s = jnp.where(visible, s, NEG_INF)

        m_prev = m_ref[:, :1]  # (block_q, 1)
        l_prev = l_ref[:, :1]
        m_cur = jnp.maximum(jnp.max(s, axis=1, keepdims=True), m_prev)
        # Fully-masked rows keep m_cur == NEG_INF; clamp the shift so
        # their p = exp(NEG_INF - 0) == 0 instead of exp(0) == 1 (same
        # guard as attention.blockwise_accumulate).
        m_safe = jnp.where(m_cur <= NEG_INF / 2, 0.0, m_cur)
        p = jnp.exp(s - m_safe)
        correction = jnp.exp(
            jnp.where(m_prev <= NEG_INF / 2, NEG_INF, m_prev) - m_safe
        )
        l_cur = l_prev * correction + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * correction + pv
        m_ref[...] = jnp.broadcast_to(m_cur, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_cur, l_ref.shape)

    _causal_dispatch(
        _compute, causal, qi, ki, block_q, block_k, q_offset, kv_offset,
        window=window,
    )

    @pl.when(ki == num_k - 1)
    def _finalize():
        l_final = l_ref[:, :1]
        l_safe = jnp.where(l_final == 0.0, 1.0, l_final)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = (
            m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-37))
        ).astype(lse_ref.dtype)


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
    """Run the pallas kernel on [BH, T, D] inputs; returns (o, lse).

    ``out_dtype`` overrides the output dtype of ``o`` (default: q's) —
    ring callers take f32 so per-step partials are not rounded to bf16
    before the cross-step merge.

    The head dim is used directly as the block lane dim — Mosaic pads
    sub-128 tiles internally, which beats explicitly zero-padding to 128
    (that would double HBM traffic and MXU passes for d=64).  The lse
    output is lane-broadcast to (bh, t_q, 128) so its block satisfies
    the TPU (8, 128) tiling rule, then lane 0 is taken.
    """
    bh, t_q, d = q.shape
    t_k = k.shape[1]
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
    grid = (bh, t_q // block_q, t_k // block_k)
    kernel = functools.partial(
        _flash_fwd_kernel,
        scale=scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        q_offset=q_offset,
        kv_offset=kv_offset,
        window=window,
    )
    scratch = [
        pltpu.VMEM((block_q, d), jnp.float32),
        pltpu.VMEM((block_q, 128), jnp.float32),
        pltpu.VMEM((block_q, 128), jnp.float32),
    ]
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_q, d), out_dtype or q.dtype),
            jax.ShapeDtypeStruct((bh, t_q, 128), jnp.float32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
    )(q, k, v)
    return o, lse[..., 0]


def _flash_bwd_dq_kernel(
    q_ref,  # (1, block_q, d)
    k_ref,  # (1, block_k, d)
    v_ref,  # (1, block_k, d)
    do_ref,  # (1, block_q, d)
    lse_ref,  # (1, block_q, 128)
    delta_ref,  # (1, block_q, 128)
    dq_ref,  # out (1, block_q, d)
    acc_ref,  # VMEM (block_q, d) f32
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    q_offset: int,
    kv_offset: int,
    window=None,
):
    """dQ = (P ∘ (dO Vᵀ − D)) K · scale, accumulated over kv blocks."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    num_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute(masked: bool):
        # Native-dtype (bf16) MXU operands, f32 accumulation — see fwd.
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if masked:
            q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = kv_offset + ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            visible = q_pos >= k_pos
            if window is not None:
                visible = visible & (q_pos - k_pos < window)
            s = jnp.where(visible, s, NEG_INF)
            # exp(s - lse); fully-masked rows have lse ~ NEG_INF — zero.
            p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - lse))
        else:
            p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta)
        acc_ref[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _causal_dispatch(
        _compute, causal, qi, ki, block_q, block_k, q_offset, kv_offset,
        window=window,
    )

    @pl.when(ki == num_k - 1)
    def _finalize():
        dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    q_ref,  # (1, block_q, d)
    k_ref,  # (1, block_k, d)
    v_ref,  # (1, block_k, d)
    do_ref,  # (1, block_q, d)
    lse_ref,  # (1, block_q, 128)
    delta_ref,  # (1, block_q, 128)
    dk_ref,  # out (1, block_k, d)
    dv_ref,  # out (1, block_k, d)
    dk_acc_ref,  # VMEM (block_k, d) f32
    dv_acc_ref,  # VMEM (block_k, d) f32
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    q_offset: int,
    kv_offset: int,
    window=None,
):
    """dV = Pᵀ dO and dK = dSᵀ Q · scale, accumulated over q blocks."""
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    num_q = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    def _compute(masked: bool):
        # Native-dtype (bf16) MXU operands, f32 accumulation — see fwd.
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (block_q, block_k)
        if masked:
            q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = kv_offset + ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            visible = q_pos >= k_pos
            if window is not None:
                visible = visible & (q_pos - k_pos < window)
            s = jnp.where(visible, s, NEG_INF)
            p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - lse))
        else:
            p = jnp.exp(s - lse)
        dv_acc_ref[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # pᵀ @ do: (block_k, d)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta)
        dk_acc_ref[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # dsᵀ @ q (un-normalized; scale applied at finalize)

    _causal_dispatch(
        _compute, causal, qi, ki, block_q, block_k, q_offset, kv_offset,
        window=window,
    )

    @pl.when(qi == num_q - 1)
    def _finalize():
        dk_ref[0] = (dk_acc_ref[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)


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


def _flash_backward_pallas(
    q, k, v, o, lse, do, *, scale: float, causal: bool,
    block_q: int, block_k: int, q_offset: int, kv_offset: int, interpret: bool,
    lse_delta_b=None, out_dtype=None, window=None,
):
    """Pallas flash backward on [BH, T, D] inputs → (dq, dk, dv).

    ``out_dtype`` overrides the gradients' dtype (default: the inputs') —
    ring callers take f32 so per-step partials are not rounded to bf16
    before cross-step accumulation.

    Two tiled kernels: dQ iterates kv blocks innermost (accumulator over
    the q row block), dK/dV iterates q blocks innermost (accumulators
    over the kv block).  ``delta = rowsum(dO ∘ O)`` and the saved lse are
    lane-broadcast to 128 so their blocks satisfy TPU (8, 128) tiling;
    pass ``lse_delta_b`` (from :func:`_lse_delta_lanes`) to reuse them
    across calls that share (o, lse, do).
    """
    bh, t_q, d = q.shape
    t_k = k.shape[1]
    block_q = min(block_q, t_q)
    block_k = min(block_k, t_k)
    if t_q % block_q or t_k % block_k:
        raise ValueError(
            f"block sizes ({block_q}, {block_k}) must divide the "
            f"sequence lengths ({t_q}, {t_k})"
        )
    if lse_delta_b is None:
        lse_delta_b = _lse_delta_lanes(o, lse, do)
    lse_b, delta_b = lse_delta_b

    common = dict(
        scale=scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        q_offset=q_offset,
        kv_offset=kv_offset,
        window=window,
    )
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **common),
        grid=(bh, t_q // block_q, t_k // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t_q, d), out_dtype or q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse_b, delta_b)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, **common),
        grid=(bh, t_k // block_k, t_q // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_k, d), out_dtype or k.dtype),
            jax.ShapeDtypeStruct((bh, t_k, d), out_dtype or v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse_b, delta_b)

    return dq, dk, dv


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10)
)
def _flash_bthd(
    q, k, v, scale, causal, block_q, block_k, q_offset, kv_offset, interpret,
    window,
):
    out, _ = _flash_fwd_bthd(
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


def _flash_fwd_bthd(
    q, k, v, scale, causal, block_q, block_k, q_offset, kv_offset, interpret,
    window,
):
    b, t, h, d = q.shape
    o, lse = _flash_forward(
        _bthd_to_bht(q),
        _bthd_to_bht(k),
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
    out = _bht_to_bthd(o, b, h)
    return out, (q, k, v, out, lse)


def _flash_bwd_bthd(
    scale, causal, block_q, block_k, q_offset, kv_offset, interpret, window,
    res, g,
):
    q, k, v, out, lse = res
    b, t, h, d = q.shape
    dq, dk, dv = _flash_backward_pallas(
        _bthd_to_bht(q),
        _bthd_to_bht(k),
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
    return _bht_to_bthd(dq, b, h), _bht_to_bthd(dk, b, h), _bht_to_bthd(dv, b, h)


_flash_bthd.defvjp(_flash_fwd_bthd, _flash_bwd_bthd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    # Defaults from an on-chip sweep (v5e, b=4 T=2048 h=16 dh=64 bf16,
    # fwd+bwd, min-of-3 over a 60-iter scan delta): 1024/1024 = 4.0 ms vs
    # 512/1024 = 4.3, 512/512 = 5.1, 128/512 = 8.8, dense = 15.6.  Large
    # tiles amortize per-step overhead; bigger (1024/2048) exceeds the
    # 16 MB scoped-VMEM limit in the dkv kernel.
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
    if interpret is None:
        interpret = _interpret_default()
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    # Blocks must divide the sequence lengths: shrink the requested size
    # to the largest 8-aligned divisor (e.g. T=1280 with block_k=1024 →
    # 640) instead of erroring on any non-multiple length.
    block_q = _fit_block(q.shape[1], block_q)
    block_k = _fit_block(k.shape[1], block_k)
    if not interpret and (block_q % 8 or block_k % 8):
        # No 8-aligned divisor exists (e.g. prime T): fail here with an
        # actionable message instead of a Mosaic tiling error downstream.
        raise ValueError(
            f"sequence lengths ({q.shape[1]}, {k.shape[1]}) admit no "
            f"8-aligned block split for the compiled TPU kernel — pad the "
            f"sequence to a multiple of 8 or use dot_product_attention"
        )
    return _flash_bthd(
        q, k, v, scale, causal, block_q, block_k,
        int(q_offset), int(kv_offset), interpret,
        None if window is None else int(window),
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
