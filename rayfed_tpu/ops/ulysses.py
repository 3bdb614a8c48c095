"""Ulysses (DeepSpeed-style) sequence parallelism via all-to-all.

Alternative to ring attention for long sequences: instead of rotating K/V
around a ring, one ``lax.all_to_all`` re-shards the activations from
sequence-sharded [B, T/n, H, D] to head-sharded [B, T, H/n, D]; each
device then runs *dense* attention for its head group over the full
sequence (one big MXU-friendly matmul chain, no per-step collectives) and
a second all-to-all restores sequence sharding.  Communication volume is
O(T·H·D/n) per device and independent of the number of ring steps; it
wins over ring attention when heads are plentiful and ICI all-to-all
bandwidth is good (the usual TPU case for H ≥ n).

Requires ``num_heads % axis_size == 0``.  Absent from the reference
(SURVEY §5.7); first-class here.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from rayfed_tpu.ops.attention import as_attn_fn, dot_product_attention


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    attn_fn=None,
) -> jax.Array:
    """Collective Ulysses attention over ``axis_name`` (inside shard_map).

    Inputs are sequence shards [B, T_local, H, D]; output likewise.
    ``attn_fn`` runs the dense per-head-group attention (defaults to
    :func:`dot_product_attention`; a pallas flash kernel drops in here).
    """
    n = lax.psum(1, axis_name)
    if q.shape[2] % n:
        raise ValueError(
            f"ulysses requires heads ({q.shape[2]}) divisible by axis size ({n})"
        )
    attn_fn = attn_fn or dot_product_attention

    def seq_to_heads(x):  # [B, T/n, H, D] -> [B, T, H/n, D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    oh = attn_fn(qh, kh, vh, causal=causal, sm_scale=sm_scale)
    # [B, T, H/n, D] -> [B, T/n, H, D]
    return lax.all_to_all(oh, axis_name, split_axis=1, concat_axis=2, tiled=True)


def make_ulysses_attention(
    mesh: Mesh,
    seq_axis: str = "sp",
    *,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    attn_fn=None,
):
    """Global-view Ulysses attention sharded over ``mesh[seq_axis]``.

    Returned fn maps [B, T, H, D] → [B, T, H, D], T sharded over
    ``seq_axis``; H must divide by the axis size.
    """
    spec = P(None, seq_axis, None, None)
    fn = functools.partial(
        ulysses_attention,
        axis_name=seq_axis,
        causal=causal,
        sm_scale=sm_scale,
        attn_fn=attn_fn,
    )
    sharded = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return as_attn_fn(sharded, causal, sm_scale, "make_ulysses_attention")
