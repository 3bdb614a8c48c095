"""Pipeline parallelism over the ``pp`` mesh axis (GPipe-style).

Equal-width pipelining the TPU way: stages are shards of a *stacked*
layer pytree over ``pp``; activations hop stage→stage with
``lax.ppermute`` inside a ``lax.scan`` over ticks — no host round trips,
no per-stage processes.  XLA overlaps the collective-permute with the
next tick's compute, so the only inherent cost is the (S−1)-tick bubble,
amortized by the number of microbatches.

Absent from the reference (SURVEY §2.10: no PP anywhere); here it is a
party-local sharding strategy: combine ``pp`` with ``dp``/``tp`` axes in
one mesh and the stage body is itself free to use tp/sp collectives.

Constraints (the classic equal-width contract):

- stage input and output shapes/dtypes are identical;
- every leaf of the stacked params has leading dim == number of stages ×
  layers-per-stage (the stage receives its slice with that leading dim).
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_collective(
    stage_params: Any,
    x_microbatches: jax.Array,
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    *,
    axis_name: str = "pp",
) -> jax.Array:
    """Collective form — call inside ``shard_map``.

    ``stage_params``: this stage's slice of the stacked params (leading
    dim = layers per stage).  ``x_microbatches``: [M, mb, ...] replicated
    across stages (only stage 0 reads it).  Returns [M, mb, ...]
    outputs, replicated across stages.
    """
    num_stages = lax.psum(1, axis_name)
    stage = lax.axis_index(axis_name)
    num_mb = x_microbatches.shape[0]
    total_ticks = num_mb + num_stages - 1
    perm = [(k, (k + 1) % num_stages) for k in range(num_stages)]

    state = jnp.zeros_like(x_microbatches[0])
    outputs = jnp.zeros_like(x_microbatches)

    def tick(carry, i):
        state, outputs = carry
        # Stage s processes microbatch (i - s) on tick i, if in range.
        mb_idx = jnp.clip(i, 0, num_mb - 1)
        x_in = jnp.where(stage == 0, x_microbatches[mb_idx], state)
        y = stage_fn(stage_params, x_in)
        # Last stage banks its finished microbatch j = i - (S-1).
        j = i - (num_stages - 1)
        banked = outputs.at[jnp.clip(j, 0, num_mb - 1)].set(y)
        outputs = jnp.where((stage == num_stages - 1) & (j >= 0), banked, outputs)
        state = lax.ppermute(y, axis_name, perm)
        return (state, outputs), None

    (state, outputs), _ = lax.scan(
        tick, (state, outputs), jnp.arange(total_ticks)
    )
    # Replicate the last stage's banked outputs to every stage.
    return lax.psum(
        jnp.where(stage == num_stages - 1, outputs, jnp.zeros_like(outputs)),
        axis_name,
    )


def make_pipeline(
    mesh: Mesh,
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    *,
    axis_name: str = "pp",
    num_microbatches: int,
):
    """Build a pipelined apply: (stacked_params, x) → y.

    ``stacked_params``: pytree whose leaves have leading dim =
    total layers (divisible by the ``pp`` axis size); sharded over
    ``axis_name`` on dim 0.  ``x``: [B, ...] with B divisible by
    ``num_microbatches``; returns [B, ...].
    """
    n_stages = mesh.shape[axis_name]

    collective = functools.partial(
        pipeline_collective, stage_fn=stage_fn, axis_name=axis_name
    )
    sharded = jax.shard_map(
        collective,
        mesh=mesh,
        in_specs=(P(axis_name), P()),
        out_specs=P(),
        check_vma=False,
    )

    def apply(stacked_params, x):
        for leaf in jax.tree_util.tree_leaves(stacked_params):
            if leaf.shape[0] % n_stages:
                raise ValueError(
                    f"stacked param leading dim {leaf.shape[0]} not divisible "
                    f"by {n_stages} pipeline stages"
                )
        b = x.shape[0]
        if b % num_microbatches:
            raise ValueError(
                f"batch {b} not divisible by {num_microbatches} microbatches"
            )
        mbs = x.reshape(num_microbatches, b // num_microbatches, *x.shape[1:])
        out = sharded(stacked_params, mbs)
        return out.reshape(b, *out.shape[2:])

    return apply


def stack_params(params_list) -> Any:
    """Stack per-layer param pytrees into one stacked tree (dim 0 = layer)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *params_list)


# ---------------------------------------------------------------------------
# 1F1B training schedule
# ---------------------------------------------------------------------------


def pipeline_train_collective(
    stage_params: Any,
    x_microbatches: jax.Array,
    target_microbatches: jax.Array,
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    loss_fn: Callable[[jax.Array, jax.Array], jax.Array],
    *,
    axis_name: str = "pp",
):
    """One-forward-one-backward training schedule — call inside shard_map.

    Each scan tick runs one forward (microbatch ``t - s``) **and** one
    backward (microbatch ``t - 2(S-1) + s``) per stage, so in steady
    state every stage alternates F/B with no separate reverse pass.
    Backward recomputes the stage forward from its saved *input* via
    ``jax.vjp`` (activation recomputation), so per-stage live memory is
    O(S) saved microbatch inputs — differentiating the GPipe scan
    instead stores residuals for every one of the M + S - 1 ticks,
    O(M) per stage.  Total ticks: M + 2(S-1).

    The last stage seeds the backward from ``loss_fn(y, target)`` of the
    microbatch it just finished (its F and B of the same microbatch land
    on the same tick).  Loss is the mean of ``loss_fn`` over microbatches.

    Returns ``(loss, param_grads)``: grads have the stage's stacked-param
    shape (sharded over ``axis_name`` like the params).
    """
    num_stages = lax.psum(1, axis_name)
    stage = lax.axis_index(axis_name)
    num_mb = x_microbatches.shape[0]
    total_ticks = num_mb + 2 * (num_stages - 1)
    # Max in-flight microbatches per stage is 2(S-1-s)+1 <= 2S-1.
    num_slots = 2 * num_stages
    perm_fwd = [(k, (k + 1) % num_stages) for k in range(num_stages)]
    perm_bwd = [(k, (k - 1) % num_stages) for k in range(num_stages)]

    mb_shape = x_microbatches.shape[1:]
    in_buf0 = jnp.zeros((num_slots,) + mb_shape, x_microbatches.dtype)
    fwd_state0 = jnp.zeros(mb_shape, x_microbatches.dtype)
    bwd_state0 = jnp.zeros(mb_shape, x_microbatches.dtype)
    grads0 = jax.tree_util.tree_map(jnp.zeros_like, stage_params)
    inv_m = 1.0 / num_mb

    def tick(carry, t):
        fwd_state, bwd_state, in_buf, grads, loss_acc = carry
        fi = t - stage  # forward microbatch index this tick
        bi = t - 2 * (num_stages - 1) + stage  # backward microbatch index
        do_f = (fi >= 0) & (fi < num_mb)
        do_b = (bi >= 0) & (bi < num_mb)

        # ---- forward ----
        x_in = jnp.where(
            stage == 0, x_microbatches[jnp.clip(fi, 0, num_mb - 1)], fwd_state
        )
        y = stage_fn(stage_params, x_in)
        # Save the stage input so backward can recompute (gated write).
        slot_f = jnp.clip(fi, 0, num_mb - 1) % num_slots
        saved = in_buf.at[slot_f].set(x_in)
        in_buf = jnp.where(do_f, saved, in_buf)

        # Last stage: loss of the microbatch finished this tick, and the
        # backward seed dL/dy for that same microbatch (fi == bi there).
        tgt = target_microbatches[jnp.clip(fi, 0, num_mb - 1)]
        mb_loss, seed = jax.value_and_grad(loss_fn)(y, tgt)
        loss_acc = loss_acc + jnp.where(
            (stage == num_stages - 1) & do_f, mb_loss * inv_m, 0.0
        )

        # ---- backward (recompute from the saved input) ----
        slot_b = jnp.clip(bi, 0, num_mb - 1) % num_slots
        x_saved = in_buf[slot_b]
        _, vjp_fn = jax.vjp(stage_fn, stage_params, x_saved)
        g_in = jnp.where(
            stage == num_stages - 1,
            seed.astype(bwd_state.dtype) * inv_m,
            bwd_state,
        )
        gp, gx = vjp_fn(g_in.astype(y.dtype))
        grads = jax.tree_util.tree_map(
            lambda acc, g: acc + jnp.where(do_b, g, jnp.zeros_like(g)),
            grads,
            gp,
        )

        fwd_state = lax.ppermute(y, axis_name, perm_fwd)
        bwd_state = lax.ppermute(gx, axis_name, perm_bwd)
        return (fwd_state, bwd_state, in_buf, grads, loss_acc), None

    carry0 = (fwd_state0, bwd_state0, in_buf0, grads0, jnp.float32(0.0))
    (_, _, _, grads, loss_acc), _ = lax.scan(
        tick, carry0, jnp.arange(total_ticks)
    )
    # Loss lives on the last stage only; replicate it.
    loss = lax.psum(
        jnp.where(stage == num_stages - 1, loss_acc, 0.0), axis_name
    )
    return loss, grads


# ---------------------------------------------------------------------------
# Interleaved (virtual-stage) schedule
# ---------------------------------------------------------------------------


def pipeline_train_interleaved_collective(
    stage_params: Any,
    x_microbatches: jax.Array,
    target_microbatches: jax.Array,
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    loss_fn: Callable[[jax.Array, jax.Array], jax.Array],
    *,
    axis_name: str = "pp",
    num_chunks: int,
):
    """Interleaved-schedule training — call inside shard_map.

    Virtual-stage pipelining (the Megatron-LM interleaved idea, built
    the SPMD way): each device hosts ``v = num_chunks`` model CHUNKS,
    with virtual stage ``s_v = c·S + d`` (chunk ``c`` on device ``d``) —
    so one pipeline traversal visits the device ring ``v`` times.  The
    ramp bubble shrinks from (S−1) full per-device stage times to
    (S−1) CHUNK times (1/v of a stage): the first microbatch reaches the
    last device after S−1 chunk computations, not S−1 stage
    computations.

    Schedule: microbatch ``m = g·S + r`` runs its (chunk ``c``) unit on
    device ``d`` at fine tick ``τ = d + g·S·v + c·S + r``.  Every
    dependency is satisfied with margin exactly 1 tick, so a single
    forward ring ``ppermute`` per tick carries both the stage→stage hop
    and the chunk-wrap hop (device S−1 → device 0), and every device is
    busy every tick in steady state.  The backward pass is the exact
    time-reversal of the forward schedule on the reverse ring; each
    backward unit recomputes its chunk forward from the saved chunk
    INPUT (activation recomputation), so per-device live memory is the
    M·v saved chunk inputs — GPipe-with-recompute's O(M) class, traded
    for the interleaved bubble; use the 1F1B schedule (v=1) when
    activation memory, not bubble, binds.

    Total fine ticks: 2·(M·v + S − 1); ideal step time
    2·M·T_stage + 2·(S−1)·T_stage/v vs 1F1B's 2·M·T + 2·(S−1)·T.

    Returns ``(loss, param_grads)`` like
    :func:`pipeline_train_collective`; the device's param slice is
    [v·layers_per_chunk, ...] with its chunks CONTIGUOUS in chunk order
    (see ``_interleave_blocks`` in :func:`make_pipeline_train`).
    """
    num_stages = lax.psum(1, axis_name)
    stage = lax.axis_index(axis_name)
    v = num_chunks
    num_mb = x_microbatches.shape[0]
    span = num_mb * v + num_stages - 1  # fine ticks per direction
    perm_fwd = [(k, (k + 1) % num_stages) for k in range(num_stages)]
    perm_bwd = [(k, (k - 1) % num_stages) for k in range(num_stages)]

    mb_shape = x_microbatches.shape[1:]
    inv_m = 1.0 / num_mb

    def chunk_params(c):
        # Static per-branch chunk slice: leading dim v*Lc -> [Lc, ...].
        def slice_c(p):
            lc = p.shape[0] // v
            return p[c * lc : (c + 1) * lc]

        return jax.tree_util.tree_map(slice_c, stage_params)

    def decode_unit(u):
        """Fine-tick offset u = τ − d → (chunk, microbatch, valid)."""
        g = u // (num_stages * v)
        rem = u % (num_stages * v)
        c = rem // num_stages
        r = rem % num_stages
        m = g * num_stages + r
        valid = (u >= 0) & (m >= 0) & (m < num_mb)
        return c, jnp.clip(m, 0, num_mb - 1), valid

    # ---- forward: compute + save every chunk input --------------------------
    in_store0 = jnp.zeros((v, num_mb) + mb_shape, x_microbatches.dtype)

    def fwd_tick(carry, tau):
        state, in_store, loss_acc = carry
        u = tau - stage
        c, m, valid = decode_unit(u)
        # Fresh microbatches enter only at virtual stage 0 (= device 0
        # chunk 0); every other unit consumes the ring.
        x_in = jnp.where(
            (stage == 0) & (c == 0), x_microbatches[m], state
        )
        y = lax.switch(
            c, [lambda x, cc=cc: stage_fn(chunk_params(cc), x) for cc in range(v)],
            x_in,
        )
        saved = jax.lax.dynamic_update_slice(
            in_store, x_in[None, None], (c, m) + (0,) * len(mb_shape)
        )
        in_store = jnp.where(valid, saved, in_store)
        # Loss banks at the LAST virtual stage (device S−1, chunk v−1).
        mb_loss = loss_fn(y, target_microbatches[m])
        loss_acc = loss_acc + jnp.where(
            (stage == num_stages - 1) & (c == v - 1) & valid,
            mb_loss * inv_m,
            0.0,
        )
        state = lax.ppermute(y, axis_name, perm_fwd)
        return (state, in_store, loss_acc), None

    carry0 = (
        jnp.zeros(mb_shape, x_microbatches.dtype),
        in_store0,
        jnp.float32(0.0),
    )
    (_, in_store, loss_acc), _ = lax.scan(
        fwd_tick, carry0, jnp.arange(span)
    )

    # ---- backward: exact time-reversal of the forward schedule --------------
    grads0 = jax.tree_util.tree_map(jnp.zeros_like, stage_params)

    def bwd_tick(carry, tau_b):
        g_state, grads = carry
        u = (span - 1 - tau_b) - stage  # the unit whose forward slot mirrors
        c, m, valid = decode_unit(u)
        x_saved = jax.lax.dynamic_slice(
            in_store, (c, m) + (0,) * len(mb_shape), (1, 1) + mb_shape
        ).reshape(mb_shape)

        def branch(cc):
            def run(x_saved, g_in, tgt):
                p_c = chunk_params(cc)
                y, vjp_fn = jax.vjp(
                    lambda p, x: stage_fn(p, x), p_c, x_saved
                )
                # Seed at the last virtual stage: dL/dy of this unit's
                # own microbatch; elsewhere the ring cotangent.
                gy = jax.grad(loss_fn)(y, tgt)
                is_seed = (stage == num_stages - 1) & (cc == v - 1)
                g_eff = jnp.where(
                    is_seed, gy.astype(g_in.dtype) * inv_m, g_in
                )
                gp_c, gx = vjp_fn(g_eff.astype(y.dtype))
                # Embed the chunk grads into the device's full slice.
                def embed(full, gc):
                    lc = full.shape[0] // v
                    return jax.lax.dynamic_update_slice(
                        full, gc, (cc * lc,) + (0,) * (full.ndim - 1)
                    )

                gp = jax.tree_util.tree_map(
                    embed,
                    jax.tree_util.tree_map(jnp.zeros_like, stage_params),
                    gp_c,
                )
                return gp, gx

            return run

        gp, gx = lax.switch(
            c,
            [branch(cc) for cc in range(v)],
            x_saved,
            g_state,
            target_microbatches[m],
        )
        grads = jax.tree_util.tree_map(
            lambda acc, g: acc + jnp.where(valid, g, jnp.zeros_like(g)),
            grads,
            gp,
        )
        g_state = lax.ppermute(
            jnp.where(valid, gx, jnp.zeros_like(gx)), axis_name, perm_bwd
        )
        return (g_state, grads), None

    (_, grads), _ = lax.scan(
        bwd_tick,
        (jnp.zeros(mb_shape, x_microbatches.dtype), grads0),
        jnp.arange(span),
    )
    loss = lax.psum(
        jnp.where(stage == num_stages - 1, loss_acc, 0.0), axis_name
    )
    return loss, grads


def make_pipeline_train(
    mesh: Mesh,
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    loss_fn: Callable[[jax.Array, jax.Array], jax.Array],
    *,
    axis_name: str = "pp",
    num_microbatches: int,
    virtual_stages: int = 1,
):
    """Build a pipelined training step: (stacked_params, x, targets) → (loss, grads).

    ``loss_fn(y_mb, target_mb) -> scalar``; the returned loss is its mean
    over microbatches and ``grads`` matches ``stacked_params`` (sharded
    over ``axis_name``).  Gradient-equivalent to ``jax.grad`` through the
    :func:`make_pipeline` forward (tested).

    ``virtual_stages=1`` (default): the 1F1B schedule — O(S) per-stage
    activation memory, ramp bubble 2(S−1) stage times.
    ``virtual_stages=v>1``: the interleaved schedule — each device hosts
    ``v`` model chunks and the bubble shrinks to 2(S−1)/v stage times
    (see :func:`pipeline_train_interleaved_collective`); ``stage_fn``
    then receives chunks of ``total_layers/(S·v)`` layers.
    """
    n_stages = mesh.shape[axis_name]
    v = int(virtual_stages)
    if v < 1:
        raise ValueError(f"virtual_stages must be >= 1, got {v}")

    if v == 1:
        collective = functools.partial(
            pipeline_train_collective,
            stage_fn=stage_fn,
            loss_fn=loss_fn,
            axis_name=axis_name,
        )
    else:
        collective = functools.partial(
            pipeline_train_interleaved_collective,
            stage_fn=stage_fn,
            loss_fn=loss_fn,
            axis_name=axis_name,
            num_chunks=v,
        )
    sharded = jax.shard_map(
        collective,
        mesh=mesh,
        in_specs=(P(axis_name), P(), P()),
        out_specs=(P(), P(axis_name)),
        check_vma=False,
    )

    def _interleave_blocks(leaf):
        """Reorder virtual-stage blocks so shard_map's contiguous split
        hands device d its chunks [d, S+d, …] in chunk order."""
        lb = leaf.shape[0] // (n_stages * v)
        blocks = leaf.reshape((n_stages * v, lb) + leaf.shape[1:])
        order = jnp.asarray(
            [c * n_stages + d for d in range(n_stages) for c in range(v)]
        )
        return jnp.take(blocks, order, axis=0).reshape(leaf.shape)

    def _deinterleave_blocks(leaf):
        lb = leaf.shape[0] // (n_stages * v)
        blocks = leaf.reshape((n_stages * v, lb) + leaf.shape[1:])
        order = [c * n_stages + d for d in range(n_stages) for c in range(v)]
        inverse = jnp.asarray(
            [order.index(b) for b in range(n_stages * v)]
        )
        return jnp.take(blocks, inverse, axis=0).reshape(leaf.shape)

    def train(stacked_params, x, targets):
        for leaf in jax.tree_util.tree_leaves(stacked_params):
            if leaf.shape[0] % (n_stages * v):
                raise ValueError(
                    f"stacked param leading dim {leaf.shape[0]} not divisible "
                    f"by {n_stages} stages x {v} virtual stages"
                )
        b = x.shape[0]
        if b % num_microbatches:
            raise ValueError(
                f"batch {b} not divisible by {num_microbatches} microbatches"
            )
        if v > 1 and num_microbatches % n_stages:
            # The interleaved slot formula m = g*S + r schedules
            # microbatches in groups of S; a trailing partial group's
            # units would land past the scan span and silently drop
            # their loss/grad contributions (same constraint as
            # Megatron-LM's interleaved schedule).
            raise ValueError(
                f"interleaved schedule needs num_microbatches "
                f"({num_microbatches}) divisible by the {n_stages} "
                f"pipeline stages (virtual_stages={v})"
            )
        mb = b // num_microbatches
        mbs = x.reshape(num_microbatches, mb, *x.shape[1:])
        tgts = targets.reshape(num_microbatches, mb, *targets.shape[1:])
        if v == 1:
            return sharded(stacked_params, mbs, tgts)
        permuted = jax.tree_util.tree_map(_interleave_blocks, stacked_params)
        loss, grads = sharded(permuted, mbs, tgts)
        return loss, jax.tree_util.tree_map(_deinterleave_blocks, grads)

    return train
