"""Reference multi-head attention + the blockwise online-softmax core.

All attention in the framework flows through two functions:

- :func:`dot_product_attention` — the plain O(T²) reference used for
  testing and short sequences; einsum-based so XLA maps it onto the MXU.
- :func:`blockwise_accumulate` — one online-softmax accumulation step
  over a K/V block.  Ring attention (``ring_attention.py``) uses it with
  K/V blocks arriving over ``ppermute``; it is the same recurrence a
  flash-attention kernel runs per tile (m/l/o running max, normalizer,
  weighted sum — numerically identical to full softmax).

Layout convention everywhere: ``[batch, seq, heads, head_dim]`` (BTHD).
Accumulation is float32 regardless of input dtype (bf16-safe).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30  # large-negative instead of -inf: keeps exp() flushable


def kv_group(q: jax.Array, k: jax.Array, v: jax.Array) -> int:
    """Query heads a K/V head serves: ``k``/``v`` are ``[B, T, KV, D]``
    with ``KV`` dividing ``q``'s ``H`` (grouped-query attention; query
    head ``h`` reads K/V head ``h // (H // KV)``).  1 for plain MHA."""
    h, kv = q.shape[2], k.shape[2]
    if h % kv or v.shape[2] != kv:
        raise ValueError(
            f"K/V heads ({kv}, {v.shape[2]}) must agree and divide the "
            f"query heads ({h})"
        )
    return h // kv


def repeat_kv(q: jax.Array, k: jax.Array, v: jax.Array):
    """Grouped K/V repeated to ``q``'s heads, for an attention that
    wants equal head counts (ring, Ulysses); the flash kernel and
    :func:`dot_product_attention` read grouped K/V as it is."""
    group = kv_group(q, k, v)
    if group == 1:
        return k, v
    return jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)


def check_score_parts(q, k, v: jax.Array) -> int:
    """A score given in PARTS: ``q`` a tuple of ``q_p`` ``[B, T, H, D_p]``,
    ``k`` of ``k_p`` ``[B, T, KV_p, D_p]``, the score the sum over parts
    of ``q_p · k_p``.  Latent attention's key is such a pair: a part a
    head of its own, and a rotary part that ONE head holds for all of
    them.  Raises unless the widths pair up and every K/V head count
    divides the others' and the query heads; returns the most heads a
    key part has."""
    if len(q) != len(k) or not q:
        raise ValueError(f"{len(q)} query parts for {len(k)} key parts")
    h = q[0].shape[2]
    for q_p, k_p in zip(q, k):
        if q_p.shape[-1] != k_p.shape[-1] or q_p.shape[2] != h:
            raise ValueError(
                f"score part shapes {q_p.shape} and {k_p.shape} do not pair"
            )
    heads = max(k_p.shape[2] for k_p in k)
    for x in (*k, v):
        if h % x.shape[2] or heads % x.shape[2]:
            raise ValueError(
                f"K/V heads ({[x.shape[2] for x in (*k, v)]}) must divide "
                f"one another and the query heads ({h})"
            )
    return heads


def score_parts(q, k, v: jax.Array):
    """The parts of a score (:func:`check_score_parts`) as one ``(q,
    k)``: laid side by side, every ``k_p`` repeated to the most heads a
    part has.  The flash kernel reads the parts as they are."""
    heads = check_score_parts(q, k, v)
    return jnp.concatenate(q, axis=-1), jnp.concatenate(
        [jnp.repeat(k_p, heads // k_p.shape[2], axis=2) for k_p in k], axis=-1
    )


def dot_product_attention(
    q,  # an array, or a tuple of score parts (:func:`score_parts`)
    k,
    v: jax.Array,
    *,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    mask: Optional[jax.Array] = None,
    q_offset: int = 0,
    kv_offset: int = 0,
    window: Optional[int] = None,
) -> jax.Array:
    """Plain softmax attention, BTHD layout.

    ``q_offset``/``kv_offset`` are the global positions of the first query
    / key token — used when q and k are shards of a longer sequence (the
    causal mask must compare *global* positions).  ``window`` (requires
    ``causal``) restricts each query to its last ``window`` keys.
    ``k``/``v`` may hold fewer (grouped) heads than ``q``
    (:func:`kv_group`); the group is a dimension of the contractions,
    never a repeated copy.  ``v``'s width may differ from the query-key
    width (the output has ``v``'s), and ``q``/``k`` may be tuples of
    score parts (:func:`score_parts`), as for the flash kernel.
    """
    if isinstance(q, (tuple, list)):
        q, k = score_parts(q, k, v)
    if window is not None:
        if not causal:
            raise ValueError("window= requires causal=True")
        if window < 1:
            # Same contract as flash_attention: window=0 would mask every
            # score, and softmax of an all-NEG_INF row is silently uniform.
            raise ValueError(f"window must be >= 1, got {window}")
    orig_dtype = q.dtype
    head_dim = q.shape[-1]
    scale = sm_scale if sm_scale is not None else head_dim**-0.5
    b, t_q, h, _ = q.shape
    t_k, kv, group = k.shape[1], k.shape[2], kv_group(q, k, v)
    qf = (q.astype(jnp.float32) * scale).reshape(b, t_q, kv, group, head_dim)
    s = jnp.einsum("bqngd,bknd->bngqk", qf, k.astype(jnp.float32))
    s = s.reshape(b, h, t_q, t_k)
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[1])
        k_pos = kv_offset + jnp.arange(k.shape[1])
        causal_mask = q_pos[:, None] >= k_pos[None, :]
        if window is not None:
            causal_mask = causal_mask & (
                q_pos[:, None] - k_pos[None, :] < window
            )
        s = jnp.where(causal_mask[None, None, :, :], s, NEG_INF)
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    # Guard fully-masked rows (can happen for causal shards where every
    # key is in the future): softmax of all-NEG_INF must yield zeros.
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.max(s, axis=-1, keepdims=True) <= NEG_INF / 2, 0.0, p)
    o = jnp.einsum(
        "bngqk,bknd->bqngd",
        p.reshape(b, kv, group, t_q, t_k), v.astype(jnp.float32),
    )
    return o.reshape(b, t_q, h, v.shape[-1]).astype(orig_dtype)


def as_attn_fn(sharded, built_causal: bool, built_scale, builder: str):
    """Give a shard_map'd (q, k, v) attention the ``attn_fn`` signature.

    Model code (:func:`mha`, ``apply_llama``) calls ``attn_fn(q, k, v,
    causal=..., sm_scale=...)``; a ring/Ulysses builder bakes masking and
    scale in at build time, so the wrapper accepts those kwargs and
    rejects *conflicting* values instead of silently ignoring them.
    Grouped K/V is repeated to the query heads here (:func:`repeat_kv`),
    so a model hands every ``attn_fn`` the same unrepeated K/V.
    """

    def apply(q, k, v, *, causal=None, sm_scale=None, mask=None, window=None):
        if mask is not None:
            raise ValueError(
                f"{builder} attention does not support a dense mask"
            )
        if window is not None:
            # Accepted-then-rejected so LlamaConfig(sliding_window=...)
            # with a ring/Ulysses attn_fn fails with this explanation,
            # not a bare unexpected-keyword TypeError.
            raise ValueError(
                f"{builder} attention does not support sliding-window "
                f"attention (window={window}); drop sliding_window or use "
                f"the flash/dense attention path"
            )
        if causal is not None and bool(causal) != built_causal:
            raise ValueError(
                f"causal={causal} conflicts with the {builder}(...) "
                f"build-time setting causal={built_causal}"
            )
        if sm_scale is not None:
            # A builder given sm_scale=None applies the conventional
            # d**-0.5 — an explicit caller value equal to that effective
            # scale is agreement, not conflict.
            effective = (
                built_scale if built_scale is not None
                else q.shape[-1] ** -0.5
            )
            # isclose, not ==: 1/math.sqrt(d), d**-0.5, and an f32-stored
            # copy of either differ by ulps — agreement, not conflict.
            # rel_tol covers float32 provenance (~1e-7 ulp).
            if not math.isclose(sm_scale, effective, rel_tol=1e-6):
                raise ValueError(
                    f"sm_scale={sm_scale} conflicts with the {builder}(...) "
                    f"build-time scale {effective}"
                )
        return sharded(q, *repeat_kv(q, k, v))

    return apply


def mha(
    x: jax.Array,
    wq: jax.Array,
    wk: jax.Array,
    wv: jax.Array,
    wo: jax.Array,
    *,
    num_heads: int,
    causal: bool = False,
    attn_fn=None,
) -> jax.Array:
    """Full MHA block: project, attend, merge.  ``x``: [B, T, D_model].

    ``wq/wk/wv``: [D_model, H*Dh]; ``wo``: [H*Dh, D_model].  ``attn_fn``
    lets callers swap in ring/Ulysses/pallas attention (same signature as
    :func:`dot_product_attention`).
    """
    b, t, d_model = x.shape
    attn_fn = attn_fn or dot_product_attention
    q = (x @ wq).reshape(b, t, num_heads, -1)
    k = (x @ wk).reshape(b, t, num_heads, -1)
    v = (x @ wv).reshape(b, t, num_heads, -1)
    o = attn_fn(q, k, v, causal=causal)
    return o.reshape(b, t, -1) @ wo


def blockwise_accumulate(
    q: jax.Array,
    k_blk: jax.Array,
    v_blk: jax.Array,
    o_acc: jax.Array,
    m_acc: jax.Array,
    l_acc: jax.Array,
    *,
    scale: float,
    q_offset,
    kv_offset,
    causal: bool,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One online-softmax step over a K/V block (the flash recurrence).

    State: ``o_acc`` [B,Tq,H,D] un-normalized output, ``m_acc``/``l_acc``
    [B,H,Tq] running row-max / normalizer, all float32.  ``q_offset`` /
    ``kv_offset`` may be traced scalars (ring step index × block length);
    the global-position causal mask also handles fully-future blocks
    (every element masked → zero contribution via the m/l guards below).
    """
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale, k_blk.astype(jnp.float32)
    )
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[1])
        k_pos = kv_offset + jnp.arange(k_blk.shape[1])
        causal_mask = q_pos[:, None] >= k_pos[None, :]
        s = jnp.where(causal_mask[None, None, :, :], s, NEG_INF)

    m_blk = jnp.max(s, axis=-1)  # [B,H,Tq]
    m_new = jnp.maximum(m_acc, m_blk)
    # exp(NEG_INF - NEG_INF) would be 1 on fully-masked rows; clamp the
    # shift so masked rows contribute exp(NEG_INF - 0) == 0 instead.
    m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(s - m_safe[..., None])  # [B,H,Tq,Tk]
    correction = jnp.exp(jnp.where(m_acc <= NEG_INF / 2, NEG_INF, m_acc) - m_safe)
    l_new = l_acc * correction + jnp.sum(p, axis=-1)
    o_blk = jnp.einsum("bhqk,bkhd->bqhd", p, v_blk.astype(jnp.float32))
    o_new = o_acc * correction.transpose(0, 2, 1)[..., None] + o_blk
    return o_new, m_new, l_new


def blockwise_finalize(o_acc: jax.Array, l_acc: jax.Array, dtype) -> jax.Array:
    """Normalize the accumulated output; fully-masked rows become zeros."""
    l_safe = jnp.where(l_acc == 0.0, 1.0, l_acc)
    out = o_acc / l_safe.transpose(0, 2, 1)[..., None]
    return out.astype(dtype)


def init_blockwise_state(
    q: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    b, tq, h, d = q.shape
    o = jnp.zeros((b, tq, h, d), jnp.float32)
    m = jnp.full((b, h, tq), NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, tq), jnp.float32)
    return o, m, l
