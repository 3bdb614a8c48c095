"""Mixture-of-Experts layer with expert parallelism over the ``ep`` axis.

Completes the SURVEY §2.10 parallelism checklist (DP/FSDP/TP/SP/PP/
ring/Ulysses live elsewhere; EP lives here).  TPU-first design: experts
are a stacked weight tensor ``[E, d, f]`` sharded over ``ep`` on dim 0;
routing uses dense top-k with a capacity factor so every shape is static
(XLA-friendly — no data-dependent gathers), and token dispatch/combine
are einsums against a one-hot dispatch mask, which XLA lowers to
all-to-alls when tokens and experts live on different mesh axes.

Gating: top-k softmax gating with auxiliary load-balancing loss
(Switch/GShard style).

Two layers live here.  :func:`apply_moe` is the capacity-factor layer
above (drops overflow).  :func:`apply_expert_share` is one chip's share
of a published sparse-expert layer (sigmoid scores, a selection bias
that takes no gradient, normalised and scaled weights, a shared
expert): it is told which experts it holds, routes over all of them,
and computes its own experts' part of the result for every token routed
to them — no capacity, no drop, static shapes — through grouped matrix
products on tokens sorted by expert.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

Params = Dict[str, Any]

# The checkpoint name of `route_tokens`'s selection.
SELECTED_NAME = "moe.selected"
# The checkpoint names of the gated FFN's two products (`swiglu`): the
# gate product BEFORE `silu`, and the up product, each with its LoRA term.
FFN_GATE_NAME, FFN_UP_NAME = "ffn.gate", "ffn.up"


@dataclasses.dataclass(frozen=True)
class MoeConfig:
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    d_model: int = 64
    d_ff: int = 256
    aux_loss_weight: float = 0.01


def init_moe(key: jax.Array, config: MoeConfig) -> Params:
    e, d, f = config.num_experts, config.d_model, config.d_ff
    k_gate, k_in, k_out = jax.random.split(key, 3)
    return {
        "gate": jax.random.normal(k_gate, (d, e)) * d**-0.5,
        "w_in": jax.random.normal(k_in, (e, d, f)) * d**-0.5,
        "w_out": jax.random.normal(k_out, (e, f, d)) * f**-0.5,
    }


# Experts shard over ep; inner dims over tp when present.
PARTITION_RULES = (
    (r"w_(in|out)$", P("ep", None, "tp")),
    (r"gate$", P(None, None)),
)


# Above this many elements, the einsum path's [B,T,k,E,C] one-hot mask is
# a memory/FLOP blowup (tens of GB at T=8192, E=64) — refuse it and point
# at the scatter path, which is the default.
_EINSUM_DISPATCH_MAX_ELEMENTS = 1 << 30


def _route(params: Params, x: jax.Array, config: MoeConfig):
    """Shared top-k routing: gate values, expert ids, capacity ranks."""
    b, t, _ = x.shape
    e, k = config.num_experts, config.top_k

    logits = x @ params["gate"].astype(x.dtype)  # [B, T, E]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)  # [B, T, k]
    # Renormalize over the selected k (GShard/Mixtral convention) so the
    # combine weights sum to 1 per token regardless of how much mass the
    # softmax put outside the top-k.
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(axis=-1, keepdims=True), 1e-9
    )

    # Position of each (token, choice) within its expert's capacity
    # buffer: 0-based rank in (t, k)-lexicographic priority order —
    # equivalent to a stable sort of assignments by expert id.
    onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.int32)  # [B, T, k, E]
    flat = onehot.reshape(b, t * k, e)
    pos_in_expert = jnp.cumsum(flat, axis=1) * flat  # 1-based rank
    pos_in_expert = pos_in_expert.reshape(b, t, k, e) - 1
    return probs, gate_vals, expert_idx, onehot, pos_in_expert


def _expert_ffn(params: Params, expert_in: jax.Array) -> jax.Array:
    """[B, E, C, d] → [B, E, C, d]; E is a batched matmul dim on the MXU."""
    h = jax.nn.gelu(
        jnp.einsum(
            "becd,edf->becf", expert_in, params["w_in"].astype(expert_in.dtype)
        )
    )
    return jnp.einsum(
        "becf,efd->becd", h, params["w_out"].astype(expert_in.dtype)
    )


def apply_moe(
    params: Params,
    x: jax.Array,
    config: MoeConfig,
    *,
    return_aux: bool = False,
    dispatch: str = "scatter",
):
    """[B, T, d] → [B, T, d] with top-k expert routing.

    Static-shape dispatch: every expert processes a fixed capacity
    ``C = ceil(k·T·cf / E)`` tokens per batch row; overflow tokens are
    dropped (standard Switch behavior) and their output falls back to 0
    for that expert slot (residual connections outside absorb this).

    ``dispatch``:

    - ``"scatter"`` (default): rank-sorted sparse dispatch — tokens
      scatter into ``[B, E, C, d]`` expert buffers (out-of-capacity
      assignments drop in the scatter itself) and combine is a gather.
      O(B·T·k·d) routing work; peak routing memory is the buffers.
    - ``"einsum"``: the GShard-style one-hot ``[B, T, k, E, C]`` mask
      einsum.  O(B·T·E·C·d) dispatch FLOPs and a mask that reaches tens
      of GB at production shapes (T=8192, E=64) — kept as the reference
      implementation for numerics tests at small shapes; guarded above
      ``_EINSUM_DISPATCH_MAX_ELEMENTS``.

    Both paths share routing, so they agree exactly (tested in
    ``tests/test_moe.py``).
    """
    b, t, d = x.shape
    e, k = config.num_experts, config.top_k
    capacity = max(1, math.ceil(config.capacity_factor * k * t / e))

    probs, gate_vals, expert_idx, onehot, pos_in_expert = _route(
        params, x, config
    )
    keep = (pos_in_expert >= 0) & (pos_in_expert < capacity)

    if dispatch == "scatter":
        # Per-assignment expert rank: [B, T, k] (rank under ITS expert).
        pos_assign = jnp.max(pos_in_expert * onehot, axis=-1)
        bidx = jnp.arange(b)[:, None, None]  # [B, 1, 1] broadcasts to [B,T,k]
        # Scatter tokens into capacity buffers; ranks >= C fall outside
        # the buffer and XLA's "drop" mode discards them — the capacity
        # discipline costs no mask tensor at all.
        expert_in = jnp.zeros((b, e, capacity, d), x.dtype)
        x_rep = jnp.broadcast_to(x[:, :, None, :], (b, t, k, d))
        expert_in = expert_in.at[bidx, expert_idx, pos_assign].add(
            x_rep, mode="drop"
        )
        expert_out = _expert_ffn(params, expert_in)
        # Combine: gather each assignment's output back (dropped ranks
        # gather fill=0), weight by its gate value, sum over k.
        gathered = expert_out.at[bidx, expert_idx, pos_assign].get(
            mode="fill", fill_value=0
        )  # [B, T, k, d]
        out = jnp.sum(gathered * gate_vals[..., None].astype(x.dtype), axis=2)
    elif dispatch == "einsum":
        mask_elements = b * t * k * e * capacity
        if mask_elements > _EINSUM_DISPATCH_MAX_ELEMENTS:
            raise ValueError(
                f"einsum dispatch mask would hold {mask_elements} elements "
                f"([B={b}, T={t}, k={k}, E={e}, C={capacity}]); use "
                f'dispatch="scatter" at this scale'
            )
        # Dispatch mask [B, T, k, E, C] — one-hot over capacity slots.
        pos_clamped = jnp.clip(pos_in_expert, 0, capacity - 1)
        dispatch_mask = (
            jax.nn.one_hot(pos_clamped, capacity, dtype=x.dtype)
            * keep[..., None].astype(x.dtype)
            * onehot[..., None].astype(x.dtype)
        )  # [B, T, k, E, C]
        dispatch_tok = dispatch_mask.sum(axis=2)  # [B, T, E, C]
        combine = (
            dispatch_mask * gate_vals[..., None, None].astype(x.dtype)
        ).sum(axis=2)  # [B, T, E, C]
        expert_in = jnp.einsum("btec,btd->becd", dispatch_tok, x)
        expert_out = _expert_ffn(params, expert_in)
        out = jnp.einsum("btec,becd->btd", combine, expert_out)
    else:
        raise ValueError(f"unknown dispatch mode {dispatch!r}")

    if not return_aux:
        return out
    # Load-balancing auxiliary loss (Switch eq. 4): E * sum_e f_e * P_e.
    top1 = expert_idx[..., 0]
    frac_tokens = jnp.mean(
        jax.nn.one_hot(top1, e, dtype=jnp.float32), axis=(0, 1)
    )
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux = config.aux_loss_weight * e * jnp.sum(frac_tokens * frac_probs)
    return out, {
        "aux_loss": aux,
        "dropped_fraction": 1.0
        - jnp.mean(keep.any(axis=-1).astype(jnp.float32)),
    }


# ---------------------------------------------------------------------------
# One chip's share of a sparse-expert layer (expert parallelism's unit)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ExpertShareConfig:
    """A routed + shared expert layer of which ``held`` experts live here.

    ``num_experts`` is the router's width (every expert of the layer,
    held or not); ``held`` the ids whose weights this chip has.  The
    layer selects ``top_k`` of all ``num_experts`` per token and computes
    the part of the result its own experts give; what the absent ones
    would add is another chip's to compute and is not stood in for.
    The held experts' matrices are the frozen base of an adapter
    fine-tune: :func:`apply_expert_share` stops their gradient (their
    weight-gradient grouped product is not built).
    """

    num_experts: int = 128
    held: Tuple[int, ...] = tuple(range(16))
    top_k: int = 8
    d_model: int = 2048
    d_ff: int = 1024  # width of one routed expert
    route_scale: float = 1.0
    # The shared expert's width; None: ``d_ff``.
    shared_d_ff: Optional[int] = None
    # The width the routed experts run in (NVIDIA's LatentMoE): the
    # layer projects its input down to it and the routed sum back up, by
    # two matrices of its own (``w_lat_in``, ``w_lat_out``); the router
    # and the shared expert read the input at ``d_model``.  None: no
    # projection, the experts run at ``d_model``.
    latent: Optional[int] = None
    # "swiglu": ``(silu(x Wg) * (x Wu)) Wd``, three matrices an expert;
    # "relu2": ``relu(x Wu)^2 Wd``, two (the shared expert alike).
    activation: str = "swiglu"

    def __post_init__(self):
        if len(set(self.held)) != len(self.held) or not self.held:
            raise ValueError(f"held expert ids must be distinct: {self.held}")
        if not all(0 <= e < self.num_experts for e in self.held):
            raise ValueError(
                f"held ids {self.held} outside the router's "
                f"{self.num_experts} outputs"
            )
        if self.activation not in ACTIVATIONS:
            raise ValueError(
                f"unknown expert activation {self.activation!r}: one of "
                f"{', '.join(ACTIVATIONS)}"
            )

    @property
    def shared_width(self) -> int:
        return self.d_ff if self.shared_d_ff is None else self.shared_d_ff

    @property
    def routed_width(self) -> int:
        """The width the routed experts read and write."""
        return self.d_model if self.latent is None else self.latent


def init_expert_share(key: jax.Array, config: ExpertShareConfig,
                      dtype=jnp.float32) -> Params:
    """Random share: router over all experts, a non-zero selection bias
    (so that a path that drops it is seen), the held experts stacked on
    dim 0, one shared expert, and with ``latent`` the pair of matrices
    into and out of it.  Normal, ``fan_in ** -0.5``."""
    c = config
    e, d, f, fs = c.num_experts, c.d_model, c.d_ff, c.shared_width
    r = c.routed_width
    n = len(c.held)
    ks = jax.random.split(key, 8)

    def dense(key, *shape, fan_in):
        return (jax.random.normal(key, shape) * fan_in**-0.5).astype(dtype)

    params = {
        "router": dense(ks[0], d, e, fan_in=d),
        "router_bias": jax.random.normal(ks[1], (e,), jnp.float32) * 0.1,
        "experts": {
            "w_up": dense(ks[3], n, r, f, fan_in=r),
            "w_down": dense(ks[4], n, f, r, fan_in=f),
        },
        "shared": {
            "w_up": dense(ks[6], d, fs, fan_in=d),
            "w_down": dense(ks[7], fs, d, fan_in=fs),
        },
    }
    if c.activation == "swiglu":  # relu2 has no gate matrix
        params["experts"]["w_gate"] = dense(ks[2], n, r, f, fan_in=r)
        params["shared"]["w_gate"] = dense(ks[5], d, fs, fan_in=d)
    if c.latent is not None:
        k_in, k_out = jax.random.split(jax.random.fold_in(key, 1))
        params["w_lat_in"] = dense(k_in, d, r, fan_in=d)
        params["w_lat_out"] = dense(k_out, r, d, fan_in=r)
    return params


def route_tokens(params: Params, x: jax.Array, config: ExpertShareConfig):
    """``x`` [N, d] -> (``selected`` [N, k] expert ids, ``weights``
    [N, k], ``scores`` [N, E]).

    Scores are ``sigmoid`` of the float32 router output; the selection
    is the top ``k`` of ``scores + params["router_bias"]`` (the bias
    steers load and takes no gradient; it never enters the weights); the
    weights are the selected scores normalised over ALL ``k`` selected
    experts, held here or not, times ``route_scale``.  The selection
    carries the checkpoint name ``"moe.selected"`` (``SELECTED_NAME``):
    a rematerialised layer must save it (``llama.REMAT_SAVED``, which
    ``decoder.apply_decoder`` applies, does), or its backward
    pass selects again, from scores that another fusion of the same
    arithmetic rounds differently, and differentiates another function
    than the forward pass computed.
    """
    logits = jax.lax.dot_general(
        x, params["router"].astype(x.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    scores = jax.nn.sigmoid(logits)
    biased = scores + params["router_bias"].astype(jnp.float32)
    _, selected = jax.lax.top_k(jax.lax.stop_gradient(biased), config.top_k)
    selected = checkpoint_name(selected, SELECTED_NAME)
    # The selected scores through a one-hot product, not a gather: on
    # the TPU a gather of 65,536 scalars (and its scatter-add backward)
    # costs a millisecond, the fused product nothing.
    chosen = jax.nn.one_hot(selected, scores.shape[-1], dtype=scores.dtype)
    picked = jnp.einsum("ne,nke->nk", scores, chosen)
    weights = config.route_scale * picked / (
        picked.sum(axis=-1, keepdims=True) + 1e-20
    )
    return selected, weights, scores


def _take_rows_impl(x, rows):
    # Out-of-range row ids (padding) read zeros.
    return jnp.take(x, rows, axis=0, mode="fill", fill_value=0)


def _sum_rows_impl(y, rows, n_out):
    # Out-of-range row ids (padding) are dropped.  A scatter-add of the
    # chunk's rows: measured on the chip at a quarter of the time of the
    # gather form (every token reading its top_k possible rows).
    out = jnp.zeros((n_out, y.shape[1]), jnp.float32)
    return out.at[rows].add(y.astype(jnp.float32), mode="drop").astype(y.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _take_rows(x, rows, n_out):
    """``out[r] = x[rows[r]]``: the dispatch gather (``n_out`` is
    ``len(x)``).  Its transpose is :func:`_sum_rows`, written out so
    that the pair stays a gather and a scatter-add of one chunk's rows
    whichever way autodiff turns them."""
    return _take_rows_impl(x, rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _sum_rows(y, rows, n_out):
    """``out[t] = sum of y[r] over rows[r] == t``, ``t < n_out``: the
    combine, transpose of :func:`_take_rows`."""
    return _sum_rows_impl(y, rows, n_out)


_take_rows.defvjp(
    lambda x, rows, n_out: (_take_rows_impl(x, rows), rows),
    lambda n_out, rows, g: (_sum_rows(g, rows, n_out), None),
)
_sum_rows.defvjp(
    lambda y, rows, n_out: (_sum_rows_impl(y, rows, n_out), rows),
    lambda n_out, rows, g: (_take_rows(g, rows, n_out), None),
)


def _grouped_impl() -> str:
    """Which grouped product the sorted rows go through: ``"megablox"``
    (the Pallas gmm jax ships; visits only the row tiles of held
    experts) where kernels compile, ``"ragged"`` (``jax.lax.ragged_dot``)
    on the CPU.  (The CPU tests of the kernel's wiring make it return
    ``"megablox-interpret"``.)"""
    return "ragged" if jax.default_backend() == "cpu" else "megablox"


# Row, contraction and column tile of the megablox product, at most:
# where K is no more than 2,048 an expert's whole [K, 512] column block
# stays in fast memory while its row tiles pass, so the weights are read
# once a group; a larger K (7,168) is walked in its largest lane-aligned
# divisor under that (`_contraction_tile`: 1,792, four steps), since a
# tile that does not divide K is masked on every step.  Not swept on the
# chip; the products read 45% of the bf16 peak with it at 512 rows an
# expert of K = 2,048 (PERF.md section 5).
GMM_TILING = (512, 2048, 512)
# The v5e MXU's width, which is also the lane width: the columns of a
# product the systolic array fills in one pass.
LANES = 128
# The sorted rows pass through the experts in chunks sized for the
# expected held assignments of a call (tokens * top_k * held /
# num_experts) times this headroom; a call takes as many chunks as its
# assignments fill, so nothing is ever dropped.  A second chunk costs a
# whole gather, product and scatter again, so the headroom is what keeps
# the usual call at one: a sequence's own preferences move its held
# share between 10 and 26% of its assignments where 12.5% is expected
# (PERF.md section 6), and 2.5 holds 31%.  Padding rows are gathered and
# scattered but not multiplied (the kernel visits held experts' tiles).
CHUNK_HEADROOM = 2.5


def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs`` [R, K] rows sorted by group, ``rhs`` [G, K, N],
    ``group_sizes`` [G + 1] whose last entry counts the trailing rows
    that belong to no group here: rows of group ``g`` times ``rhs[g]``;
    the trailing rows come out zero and are not multiplied.  Its scope,
    ``grouped_matmul``, marks the experts' base products: the roofline
    readers charge every kernel under it with one."""
    with jax.named_scope("grouped_matmul"):
        return _grouped(lhs, rhs, group_sizes)


def _grouped(lhs, rhs, group_sizes):
    """:func:`grouped_matmul` outside its scope (the LoRA bypass's
    products, :func:`_lora_by_block`)."""
    impl = _grouped_impl()
    if impl == "ragged":
        return jax.lax.ragged_dot(lhs, rhs, group_sizes[:-1])
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    tm, tk, tn = GMM_TILING
    tiling = (
        min(tm, lhs.shape[0]), _contraction_tile(lhs.shape[1], tk),
        min(tn, rhs.shape[2]),
    )
    if lhs.shape[0] % tiling[0]:
        raise ValueError(
            f"{lhs.shape[0]} sorted rows are no multiple of the row "
            f"tile {tiling[0]} (see _chunk_rows)"
        )
    return megablox.gmm(
        lhs, rhs, group_sizes, lhs.dtype, tiling,
        jnp.zeros((), jnp.int32), None, False,
        impl == "megablox-interpret",
    )


def _contraction_tile(k: int, most: int) -> int:
    """The largest multiple of 128 that divides ``k`` and is no larger
    than ``most``; ``min(k, most)`` where there is none (a ``k`` under
    ``most`` is its own tile)."""
    if k <= most:
        return k
    for tile in range(most - most % 128, 0, -128):
        if k % tile == 0:
            return tile
    return most


def lora_blocks(experts: int, rank: int) -> Tuple[int, int]:
    """``(blocks, experts a block)`` of the routed experts' LoRA bypass:
    a block is as many experts as fill the MXU's columns with their
    adapters side by side (``LANES // rank``).  One block is the dense
    form of :func:`_expert_linear`."""
    per_block = max(1, LANES // rank)
    return -(-experts // per_block), per_block


def _expert_linear(xs, w, group_sizes, row_expert, lora_entry):
    """Sorted rows through their experts' matrix ``w`` [G, in, out], plus
    the experts' LoRA bypass, in the form :func:`lora_blocks` picks from
    ``G`` and the rank.  Where one block holds every expert (``G * rank``
    no wider than the MXU) the bypass is two dense products: every row
    times all experts' ``A`` side by side ([in, G * rank]), masked to the
    row's own expert, times the stacked ``B`` (rank-sized grouped
    products would leave the MXU idle).  Wider, the dense form would
    multiply every row, padding included, by experts it does not use:
    the bypass is then two grouped products over blocks of experts
    (:func:`_lora_by_block`).  In either form an expert no row reached
    gets an exactly zero gradient."""
    out = grouped_matmul(xs, w, group_sizes)
    if lora_entry is None:
        return out
    a, b = (lora_entry[side].astype(xs.dtype) for side in "ab")
    scale = jax.lax.stop_gradient(lora_entry["scale"]).astype(xs.dtype)
    g, d_in, rank = a.shape
    blocks, per_block = lora_blocks(g, rank)
    if blocks > 1:
        return out + _lora_by_block(
            xs, a, b, group_sizes, row_expert, per_block
        ) * scale
    own = jax.nn.one_hot(row_expert, g, dtype=xs.dtype)  # padding: zeros
    u = xs @ a.transpose(1, 0, 2).reshape(d_in, g * rank)
    u = (u.reshape(-1, g, rank) * own[:, :, None]).reshape(-1, g * rank)
    return out + (u @ b.reshape(g * rank, -1)) * scale


def _lora_by_block(xs, a, b, group_sizes, row_expert, per_block):
    """``(x A_e) B_e`` for each sorted row of expert ``e`` (``a`` [G, in,
    rank], ``b`` [G, rank, out]) as two grouped products over blocks of
    ``per_block`` experts: a row times its block's ``A`` side by side
    ([in, per_block * rank]), masked to its own expert, times the block's
    stacked ``B``.  The rows are sorted by expert, so a block's rows are
    consecutive and its group size is its experts' summed; the trailing
    rows (``group_sizes[G]``) are neither multiplied nor written.  Zero
    experts fill the last block where ``per_block`` does not divide
    ``G``.  Outside ``grouped_matmul``'s scope, so that no reader
    charges a bypass product as a base one."""
    g, d_in, rank = a.shape
    blocks = -(-g // per_block)
    fill = ((0, blocks * per_block - g), (0, 0), (0, 0))
    a = jnp.pad(a, fill).reshape(blocks, per_block, d_in, rank)
    a = a.transpose(0, 2, 1, 3).reshape(blocks, d_in, per_block * rank)
    b = jnp.pad(b, fill).reshape(blocks, per_block * rank, -1)
    sizes = jnp.pad(group_sizes[:g], fill[0]).reshape(blocks, per_block)
    sizes = jnp.concatenate([sizes.sum(1), group_sizes[g:]])
    # a trailing row's own column is its expert's modulo the block, but
    # the first product leaves that row zero
    own = jax.nn.one_hot(row_expert % per_block, per_block, dtype=xs.dtype)
    u = _grouped(xs, a, sizes).reshape(-1, per_block, rank)
    u = (u * own[:, :, None]).reshape(-1, per_block * rank)
    return _grouped(u, b, sizes)


def swiglu(x, p, lget, dtype):
    """``(silu(x Wg) * (x Wu)) Wd`` with each matrix's optional LoRA
    entry from ``lget(name)``: every model's dense FFN and shared
    expert.  The two FFN-width products carry checkpoint names
    (``FFN_GATE_NAME`` before ``silu``, ``FFN_UP_NAME``): what a
    rematerialized layer's policy names it keeps and does not run again
    (``llama.REMAT_SAVED``: the up product)."""
    from rayfed_tpu.models.llama import _linear

    gate = _linear(x, p["w_gate"], lget("w_gate"), dtype)
    up = _linear(x, p["w_up"], lget("w_up"), dtype)
    gate = checkpoint_name(gate, FFN_GATE_NAME)
    up = checkpoint_name(up, FFN_UP_NAME)
    return _linear(jax.nn.silu(gate) * up, p["w_down"], lget("w_down"), dtype)


def relu2(x, p, lget, dtype):
    """``relu(x Wu)^2 Wd`` with each matrix's optional LoRA entry from
    ``lget(name)``: the non-gated FFN of the ``nemotron_h`` block's
    experts.  The one FFN-width product carries ``FFN_UP_NAME``, which a
    rematerialized layer keeps as it keeps ``swiglu``'s up product."""
    from rayfed_tpu.models.llama import _linear

    up = checkpoint_name(_linear(x, p["w_up"], lget("w_up"), dtype),
                         FFN_UP_NAME)
    return _linear(jnp.square(jax.nn.relu(up)), p["w_down"], lget("w_down"),
                   dtype)


# An expert's FFN by ``ExpertShareConfig.activation``.
ACTIVATIONS = {"swiglu": swiglu, "relu2": relu2}


def _chunk_sizes(static, c, group_sizes):
    """``[held + 1]``: of the sorted rows ``[c * rows, (c + 1) * rows)``
    how many are each held expert's, and how many (the rest) nobody's
    here: what the grouped products of chunk ``c`` are given."""
    _, n_held, rows, _ = static
    lo = c * rows
    ends = jnp.cumsum(group_sizes[:n_held])
    starts = ends - group_sizes[:n_held]
    held = jnp.clip(ends, lo, lo + rows) - jnp.clip(starts, lo, lo + rows)
    return jnp.concatenate([held, rows - held.sum(keepdims=True)])


def _routed_chunk(static, c, x, weights, experts, elora, route):
    """The held experts' part of the routed sum for the sorted rows
    ``[c * rows, (c + 1) * rows)``: gather, the activation's grouped
    products (three for swiglu, two for relu2) with their adapters,
    weighted sum back onto the tokens."""
    k, _, rows, activation = static
    n_tok, dtype = x.shape[0], x.dtype
    order, sorted_local, group_sizes = route
    lo = c * rows
    with jax.named_scope("moe.dispatch"):
        # The flat (token, choice) of each row of the chunk.  (Absent
        # experts' assignments that fall inside it are padding rows: the
        # grouped product returns zeros for them.)
        row_slot = jax.lax.dynamic_slice(order, (lo,), (rows,))
        row_expert = jax.lax.dynamic_slice(sorted_local, (lo,), (rows,))
        row_token = row_slot // k
        xs = _take_rows(x, row_token, n_tok)
        w_row = _take_rows(weights.reshape(-1, 1), row_slot, n_tok * k)
        sizes = _chunk_sizes(static, c, group_sizes)
    with jax.named_scope("moe.experts"):
        lget = lambda name: None if elora is None else elora.get(name)
        if activation == "swiglu":
            gate = _expert_linear(xs, experts["w_gate"], sizes, row_expert,
                                  lget("w_gate"))
            up = _expert_linear(xs, experts["w_up"], sizes, row_expert,
                                lget("w_up"))
            hidden = jax.nn.silu(gate) * up
        else:
            hidden = jnp.square(jax.nn.relu(_expert_linear(
                xs, experts["w_up"], sizes, row_expert, lget("w_up")
            )))
        ys = _expert_linear(hidden, experts["w_down"], sizes, row_expert,
                            lget("w_down"))
    with jax.named_scope("moe.combine"):
        ys = (ys.astype(jnp.float32) * w_row).astype(dtype)
        return _sum_rows(ys, row_token, n_tok)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _routed(static, chunks, x, weights, experts, elora, route):
    """``(out, multiplied)``: the routed sum, and per held expert the
    rows its grouped products were given.  The sorted rows pass through
    the experts in chunks of ``rows`` rows, as many (``chunks``, counted
    on the device) as the held assignments of this call fill: one as a
    rule, up to every assignment of every token when routing collapses
    onto this chip.  Shapes stay static, nothing is dropped, and the
    buffers are one chunk's.  A loop of a length only the device knows
    cannot be differentiated by tracing, so the backward pass is written
    out: the same loop, each chunk's pullback summed.  ``experts`` are
    the frozen base (see :func:`apply_expert_share`) and get a zero
    cotangent."""
    f32 = jnp.float32
    n_held = static[1]

    def body(c, carry):
        acc, multiplied = carry
        part = _routed_chunk(static, c, x, weights, experts, elora, route)
        sizes = _chunk_sizes(static, c, route[2])
        return acc + part.astype(f32), multiplied + sizes[:n_held]

    out, multiplied = jax.lax.fori_loop(
        0, chunks, body,
        (jnp.zeros(x.shape, f32), jnp.zeros((n_held,), jnp.int32)),
    )
    return out.astype(x.dtype), multiplied


def _routed_fwd(static, chunks, x, weights, experts, elora, route):
    out = _routed(static, chunks, x, weights, experts, elora, route)
    return out, (chunks, x, weights, experts, elora, route)


def _routed_bwd(static, res, cts):
    chunks, x, weights, experts, elora, route = res
    g = cts[0]  # the counts' cotangent is float0
    f32 = jnp.float32
    wide = lambda tree: jax.tree_util.tree_map(
        lambda v: jnp.zeros(v.shape, f32), tree
    )

    def body(c, acc):
        _, pull = jax.vjp(
            lambda x, weights, elora: _routed_chunk(
                static, c, x, weights, experts, elora, route
            ), x, weights, elora,
        )
        return jax.tree_util.tree_map(
            lambda a, d: a + d.astype(f32), acc, pull(g)
        )

    dx, dw, dlora = jax.lax.fori_loop(
        0, chunks, body, (wide(x), wide(weights), wide(elora))
    )
    narrow = lambda like, tree: jax.tree_util.tree_map(
        lambda v, d: d.astype(v.dtype), like, tree
    )
    return (None, dx.astype(x.dtype), dw.astype(weights.dtype),
            jax.tree_util.tree_map(jnp.zeros_like, experts),
            narrow(elora, dlora), None)


_routed.defvjp(_routed_fwd, _routed_bwd)


def _chunk_rows(n_tok: int, config: ExpertShareConfig) -> Tuple[int, int]:
    """(rows of one chunk, most chunks a call can need).  A chunk holds
    the expected held assignments of ``n_tok`` tokens with
    :data:`CHUNK_HEADROOM` to spare, rounded up to the row tile."""
    k, n_held = config.top_k, len(config.held)
    tile = GMM_TILING[0]
    worst = n_tok * min(k, n_held)
    expected = n_tok * k * n_held / config.num_experts
    rows = min(max(int(CHUNK_HEADROOM * expected), 1), worst)
    if rows >= tile:
        rows = -(-rows // tile) * tile
    return rows, -(-worst // rows)


def _latent(v, params, lora, name):
    """``v`` through one of the latent pair (``w_lat_in``: into the
    routed experts' width, ``w_lat_out``: back) with its LoRA entry."""
    from rayfed_tpu.models.llama import _linear

    with jax.named_scope("moe.latent"):
        return _linear(v, params[name], lora.get(name), v.dtype)


def apply_expert_share(
    params: Params,
    x: jax.Array,
    config: ExpertShareConfig,
    *,
    lora: Optional[Params] = None,
):
    """``x`` [N, d] -> (``out`` [N, d], ``aux``): the shared expert plus
    the held experts' part of the routed sum.

    With ``config.latent`` the routed experts run in that width: the
    input goes down through ``w_lat_in`` before the dispatch and the
    routed sum up through ``w_lat_out`` after the combine (scope
    ``moe.latent``); the router and the shared expert read ``x``.

    ``aux``: ``counts`` [held] the rows each held expert's grouped
    products were given, ``held_assignments`` () the (token, choice)
    pairs whose expert is held (nothing is dropped, so the counts sum to
    it), ``selected`` [N, k] the experts chosen, ``scores`` [N, E] the
    sigmoid scores they were chosen by.  ``lora`` mirrors ``params``
    (entries under ``experts`` and ``shared``, and ``w_lat_in`` /
    ``w_lat_out``; the router takes none).
    The held experts' matrices are a FROZEN base: their gradient is
    stopped here, so ``jax.grad`` with respect to them is zero by
    statement, not by omission.
    """
    n_tok = x.shape[0]
    k, n_held = config.top_k, len(config.held)
    lora = lora or {}

    with jax.named_scope("moe.route"):
        selected, weights, scores = route_tokens(params, x, config)
        # Local id of every (token, choice): 0..held-1, or `held` where
        # another chip has the expert (by comparison, not a table
        # look-up: scalar gathers are slow on the TPU).
        mine = selected[..., None] == jnp.asarray(config.held, jnp.int32)
        local = jnp.where(
            mine.any(-1), jnp.argmax(mine, -1).astype(jnp.int32), n_held
        ).reshape(-1)  # [N * k]
        held_assignments = jnp.sum(local < n_held, dtype=jnp.int32)
        flat = jnp.arange(n_tok * k, dtype=jnp.int32)
        # Stable sort by expert: rows of one expert are consecutive, in
        # token order; assignments of absent experts sort to the end.
        sorted_local, order = jax.lax.sort((local, flat), num_keys=1)
        group_sizes = jnp.sum(
            jax.nn.one_hot(local, n_held + 1, dtype=jnp.int32), axis=0
        )

    rows, most = _chunk_rows(n_tok, config)
    # Sentinels past the last assignment, so that every chunk is whole:
    # row -> no token (reads zeros), expert -> none held.
    pad = max(most * rows - n_tok * k, 0)
    route = (
        jnp.pad(order, (0, pad), constant_values=n_tok * k),
        jnp.pad(sorted_local, (0, pad), constant_values=n_held),
        group_sizes,
    )
    chunks = -(-held_assignments // rows)
    routed_in = _latent(x, params, lora, "w_lat_in") if config.latent else x
    out, multiplied = _routed(
        (k, n_held, rows, config.activation), chunks, routed_in, weights,
        jax.lax.stop_gradient(params["experts"]), lora.get("experts"), route,
    )
    if config.latent:
        out = _latent(out, params, lora, "w_lat_out")
    with jax.named_scope("moe.shared"):
        shared_lora = lora.get("shared", {})
        out = out + ACTIVATIONS[config.activation](
            x, params["shared"], shared_lora.get, x.dtype
        )
    aux = {
        "counts": multiplied,
        "held_assignments": held_assignments,
        "selected": selected,
        "scores": scores,
    }
    return out, aux
