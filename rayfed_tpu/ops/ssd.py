"""The selective state-space scan of a Mamba-2 mixer, in its chunked
(state-space duality) form: arXiv:2405.21060, section 6.

The recurrence, per head (``h`` is ``[P, N]``, ``h_{-1} = 0``)::

    a_t = exp(dt_t A)
    h_t = a_t h_{t-1} + dt_t x_t (x) B_t
    y_t = h_t C_t + D x_t

computed in chunks of ``Q`` tokens.  With ``l_t = sum_{s <= t} dt_s A``
inside a chunk::

    Y_intra[t] = sum_{s <= t} (C_t . B_s) exp(l_t - l_s) dt_s x_s
    S_c        = sum_s exp(l_Q - l_s) dt_s x_s (x) B_s     (the chunk's state)
    H_c        = exp(l_Q) H_{c-1} + S_c                    (carried)
    Y_inter[t] = exp(l_t) H_{c-1} C_t
    y          = Y_intra + Y_inter + D x

Every sum over tokens is a matrix product (the MXU's); the sequential
part is ``T / Q`` steps over the carried states.  Cumulative sums,
decays, ``dt`` and the carried state are float32; the products' operands
are ``x``'s type with float32 accumulation.  Plain ``jax.numpy``,
differentiated by JAX: no Pallas kernel yet (``ssm_scan_roofline`` in the
benchmark sizes what a fused one is worth).

The largest intermediate is the masked decay ``exp(l_t - l_s)`` a head,
``[B, T/Q, H, Q, Q]`` float32 (537 MB at 8,192 tokens, 64 heads, chunks
of 256); the backward pass keeps a few arrays of that shape while one
layer's backward runs.  All heads are computed at once: at the
benchmark's shapes a step's temporaries are 4.9 GB beside 4.1 GB of
resident arrays on a chip of 16.9 (PERF.md section 4), so memory asks
for no loop over blocks of heads.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from rayfed_tpu import telemetry


def scan_flops(tokens: int, heads: int, head_dim: int, state: int,
               groups: int, chunk: int) -> float:
    """FLOPs the chunked form needs FORWARD for ``tokens`` tokens, from
    shapes alone, 2 a multiply-add: the masked intra-chunk product (a
    token sees ``(Q + 1) / 2`` of its chunk), the chunk states, states to
    outputs, and ``C B^T`` a group.  Decays, cumulative sums and ``D x``
    are not matrix products and not counted."""
    visible = (chunk + 1) / 2
    return float(tokens) * (
        2 * heads * head_dim * visible  # Y_intra
        + 2 * heads * head_dim * state  # S_c
        + 2 * heads * head_dim * state  # Y_inter
        + 2 * groups * state * visible  # C . B
    )


def scan_bytes(batch: int, tokens: int, heads: int, head_dim: int,
               state: int, groups: int, itemsize: int) -> int:
    """Bytes of one call's inputs and output, each once: ``x`` and ``y``
    ``[B, T, H, P]`` and ``B``, ``C`` ``[B, T, G, N]`` in the compute
    type, ``dt`` ``[B, T, H]`` float32 (``A`` and ``D`` are a head's
    scalars)."""
    rows = batch * tokens
    return rows * (
        2 * heads * head_dim * itemsize + 2 * groups * state * itemsize
        + heads * 4
    )


def ssd_scan(x, dt, A, B, C, D, *, chunk: int):
    """``y`` [B, T, H, P] (``x``'s type) of the recurrence above.

    ``x`` [B, T, H, P]; ``dt`` [B, T, H], the time step after its
    softplus; ``A`` [H], negative; ``B``, ``C`` [B, T, G, N] with ``G``
    dividing ``H`` (head ``h`` reads group ``h // (H // G)``); ``D`` [H].
    ``T`` need not be a multiple of ``chunk``: the sequence is padded
    with steps of ``dt = 0`` (they decay nothing and add nothing) and
    the output cut.
    """
    bsz, t, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    if h % g or C.shape != B.shape or dt.shape != (bsz, t, h):
        raise ValueError(
            f"x {x.shape}, dt {dt.shape}, B {B.shape}, C {C.shape}: "
            f"groups must divide heads and B, C, dt agree"
        )
    r = h // g  # heads a group
    chunk = min(int(chunk), t)
    chunks = -(-t // chunk)
    dtype, f32 = x.dtype, jnp.float32
    if telemetry.armed():
        # One record a call traced while the recorder is armed: what the
        # scan of this call computes, from its static arguments alone.
        telemetry.emit(
            "ssm.scan",
            detail=dict(
                batch=bsz, tokens=t, chunk=chunk, chunks=chunks, heads=h,
                head_dim=p, state=n, groups=g,
                flops_forward=scan_flops(bsz * t, h, p, n, g, chunk),
                bytes_forward=scan_bytes(bsz, t, h, p, n, g, dtype.itemsize),
                # the float32 states the chunks carry
                state_bytes=bsz * chunks * h * p * n * 4,
                # the largest intermediate: the masked decay
                working_set_bytes=bsz * chunks * h * chunk * chunk * 4,
            ),
        )
    with jax.named_scope("ssm.scan"):
        pad = chunks * chunk - t
        padded = lambda v: jnp.pad(
            v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2)
        ).reshape(bsz, chunks, chunk, *v.shape[2:])
        # [B, C, Q, G, R, ...]: chunks of Q tokens, heads by group
        x = padded(x).reshape(bsz, chunks, chunk, g, r, p)
        dt = padded(dt.astype(f32)).reshape(bsz, chunks, chunk, g, r)
        b, c = padded(B.astype(dtype)), padded(C.astype(dtype))
        a, d = A.astype(f32).reshape(g, r), D.astype(f32).reshape(g, r)
        l = jnp.cumsum(dt * a, axis=2)  # l_t, inclusive; dt_s A <= 0
        xd = (x.astype(f32) * dt[..., None]).astype(dtype)  # dt_s x_s
        # Inside a chunk: (C_t . B_s) exp(l_t - l_s) for s <= t.
        cb = jnp.einsum("bctgn,bcsgn->bcgts", c, b, preferred_element_type=f32)
        lt = jnp.moveaxis(l, 2, -1)  # [B, C, G, R, Q]
        seen = jnp.tril(jnp.ones((chunk, chunk), bool))
        # masked before exp: above the diagonal l_t - l_s is positive
        span = jnp.where(seen, lt[..., :, None] - lt[..., None, :], -jnp.inf)
        m = (cb[:, :, :, None] * jnp.exp(span)).astype(dtype)  # [B, C, G, R, t, s]
        y = jnp.einsum("bcgrts,bcsgrp->bctgrp", m, xd, preferred_element_type=f32)
        # The chunk's own state, decayed to the chunk's end.
        to_end = jnp.exp(l[:, :, -1:] - l)  # exp(l_Q - l_s)
        xe = (x.astype(f32) * (dt * to_end)[..., None]).astype(dtype)
        states = jnp.einsum(
            "bcsgrp,bcsgn->bcgrpn", xe, b, preferred_element_type=f32
        )
        # Carried across chunks: H_c = exp(l_Q) H_{c-1} + S_c; a chunk's
        # outputs read H_{c-1}.
        whole = jnp.exp(l[:, :, -1])  # [B, C, G, R]

        def carry(held, step):
            s, w = step
            return w[..., None, None] * held + s, held

        _, before = jax.lax.scan(
            carry, jnp.zeros_like(states[:, 0]),
            (jnp.moveaxis(states, 1, 0), jnp.moveaxis(whole, 1, 0)),
        )
        before = jnp.moveaxis(before, 0, 1).astype(dtype)  # H_{c-1}
        inter = jnp.einsum(
            "bctgn,bcgrpn->bctgrp", c, before, preferred_element_type=f32
        )
        y = y + inter * jnp.exp(l)[..., None] + x.astype(f32) * d[..., None]
        return y.astype(dtype).reshape(bsz, chunks * chunk, h, p)[:, :t]
