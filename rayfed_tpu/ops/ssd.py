"""The selective state-space scan of a Mamba-2 mixer, in its chunked
(state-space duality) form: arXiv:2405.21060, section 6.  A Pallas TPU
kernel pair, forward and a hand-written backward behind
``jax.custom_vjp``.

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
are ``x``'s type with float32 accumulation (float32 operands multiply in
float32).

**What lives where.**  ``l`` and what follows from it a token and head
(``exp(l)``, ``dt exp(l_Q - l)``) are ``[B, T, H]`` float32 arrays, 2 MB
at the benchmark's shapes: plain ``jax.numpy`` outside the kernels
(:func:`_token_rows`), differentiated by JAX, so the kernels hold no
cumulative sum.  Every ``[Q, Q]`` array (``C B^T``, the masked decay
``exp(l_t - l_s)``, their product and, backward, their cotangents) is
made in VMEM a head at a time and never written to HBM.  The grid is
(batch, chunk, block of heads), the chunks in order forward and in
reverse backward; the carried states of all heads, ``[H P, N]`` float32,
are a VMEM scratch that lives across the grid.  The forward kernel
writes ``y`` and ``H_{c-1}`` (``[B, T/Q, H P, N]`` float32, 67 MB at
8,192 tokens, 64 heads x 64, state 128): with the call's inputs and the
token rows (17 MB) that is all the backward kernel reads; it makes the
decays again and carries ``dH``.  ``dB`` and ``dC`` are summed over a
group's heads in VMEM (the head blocks of a group are consecutive grid
steps).

**Tokens along the lanes.**  A grid step transposes its block of ``x``
``[Q, heads P]`` once to ``[heads P, Q]`` (and ``y`` back): a head is
then ``P`` whole sublane rows, and whatever scales a token (``dt``,
``exp(l)``, ``D``) is a ``[1, Q]`` row that broadcasts over sublanes for
nothing.  With tokens along the sublanes the same scalings were columns,
and spreading them over a head's lanes was 1.0 of a forward call's 1.76
ms (`tool/ssd_sweep.py`, my chip run, PR 36).  Only the decay needs ``l``
both ways; its columns are made once a grid step (:func:`_columns`).
Backward, every cotangent of a token row is a sum over sublanes: the one
sum over lanes, ``sum_s d(l_t - l_s)``, is taken as ``sum_p dy y_intra``
instead (one more product a head).  A grid step walks its heads with ONE
traced body (:func:`_heads`).

On the CPU backend (the test mode) the kernels run in Pallas interpret
mode and take any chunk; compiled, shapes must tile
(:func:`_check_tiling`, :func:`head_block`).
"""

from __future__ import annotations

import functools
import importlib
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rayfed_tpu import telemetry

# The module, not the function `ops/__init__` exports under its name:
# `_interpret_default` is read at each call, so one patch of it steers
# every kernel of the package (`tests/test_tpu_compile.py`).
_flash = importlib.import_module("rayfed_tpu.ops.flash_attention")

# Rows a token and head of `_token_rows`; a tile of 8 float32 sublanes.
_L, _DT, _E, _DTW, _D, _ROWS = 0, 1, 2, 3, 4, 8

# What a grid step of the backward kernel (the larger) may hold in VMEM by
# `step_vmem_bytes`; the head block is the largest the budget takes: 16
# heads of 64 at a state of 128 and chunks of 256 (17.0 MiB; 32 heads
# are 31.6).  One call there, forward / backward ms by head block
# (`tool/ssd_sweep.py`, my chip run, PR 36: PERF.md section 6 has the
# table): the chip's 128 MiB of VMEM would take 32.
VMEM_BUDGET_BYTES = 24 * 2 ** 20


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


def step_vmem_bytes(head_block: int, head_dim: int, state: int, chunk: int,
                    itemsize: int) -> int:
    """A grid step's working set in the backward kernel, from shapes:
    the blocks the pipeline holds twice (``x``, ``dy``, ``dx``,
    ``H_{c-1}``, the token rows and their cotangents, ``l``, ``B``,
    ``C``, ``dB``, ``dC``), the scratches (five float32 and two operand
    ``[heads P, Q]`` arrays, ``l``'s columns a lane tile each, ``d(C
    B^T)``), and the values of one head (six ``[Q, Q]`` float32 arrays)
    and of the transposes (two ``[Q, heads P]``)."""
    wide = chunk * head_block * head_dim
    blocks = 2 * (
        3 * wide * itemsize + head_block * head_dim * state * 4
        + 2 * head_block * _ROWS * chunk * 4 + head_block * chunk * 4
        + 2 * chunk * state * (itemsize + 4)
    )
    scratch = (
        wide * (5 * 4 + 2 * itemsize) + head_block * chunk * 128 * 4
        + chunk * chunk * 4
    )
    return blocks + scratch + 6 * chunk * chunk * 4 + 2 * wide * 4


def head_block(heads: int, groups: int, head_dim: int, state: int,
               chunk: int, itemsize: int, interpret: bool) -> int:
    """Heads a grid step: the largest divisor of a group's heads that
    tiles (its lanes of ``x`` a multiple of 128 and its rows of ``l`` a
    multiple of 8, or all the heads there are) and whose
    ``step_vmem_bytes`` fit the budget; the smallest that tiles where
    none fits.  Interpret mode takes any divisor.  With one group a head
    (decayed linear attention: ``B`` and ``C`` are a head's own keys and
    queries) a block is one head, whose lanes of ``x`` must fill whole
    128-lane tiles; its ``l`` is read from its token rows
    (:func:`_columns`)."""
    per_group = heads // groups
    tiles = [
        hb for hb in range(1, per_group + 1)
        if per_group % hb == 0
        and (interpret or hb == heads
             or ((hb * head_dim) % 128 == 0
                 and (hb % 8 == 0 or groups == heads)))
    ]
    if not tiles:
        raise ValueError(
            f"{per_group} heads a group of width {head_dim}: no block of "
            f"them fills whole 128-lane tiles"
        )
    fits = [
        hb for hb in tiles
        if step_vmem_bytes(hb, head_dim, state, chunk, itemsize)
        <= VMEM_BUDGET_BYTES
    ]
    return max(fits) if fits else min(tiles)


def _check_tiling(chunk, head_dim, state, groups, itemsize, heads=None):
    """What the compiled kernels ask of shapes (interpret mode asks
    nothing): a chunk's tokens are lanes of the token rows and sublanes
    of ``x``; a head is whole sublane tiles of the ``[heads P, Q]``
    arrays, the operand type's among them; a group's ``B``/``C`` columns
    are a lane block; with one group a head, a head's lanes of ``x``
    are a lane block too."""
    sublanes = 8 * 4 // itemsize
    per_head = groups > 1 and groups == heads
    if (chunk % 128 or head_dim % sublanes or (groups > 1 and state % 128)
            or (per_head and head_dim % 128)):
        raise ValueError(
            f"chunk {chunk}, head width {head_dim}, state {state}, "
            f"{groups} groups: the compiled scan needs a chunk that is a "
            f"multiple of 128, a head width that is a multiple of "
            f"{sublanes} (of 128 with one group a head), and a state that "
            f"is a multiple of 128 where groups > 1"
        )


def _token_rows(dt, A, D, chunk):
    """``[B, H, 8, T]`` float32, what the kernels read a token and head:
    ``l`` (the cumulative sum inside the chunk), ``dt``, ``exp(l)``,
    ``dt exp(l_Q - l)``, ``D``; three rows of zeros fill the tile.  And
    ``exp(l_Q)`` [B, T/Q, 1, H], the decay over a whole chunk (a scalar a
    head: the kernels read it from SMEM)."""
    bsz, t, h = dt.shape
    dtc = dt.reshape(bsz, t // chunk, chunk, h)
    l = jnp.cumsum(dtc * A, axis=2)  # l_t, inclusive; dt_s A <= 0
    to_end = jnp.exp(l[:, :, -1:] - l)  # exp(l_Q - l_s)
    rows = [l, dtc, jnp.exp(l), dtc * to_end, jnp.broadcast_to(D, l.shape)]
    rows += [jnp.zeros_like(l)] * (_ROWS - len(rows))
    rows = jnp.stack(rows, axis=-1)  # [B, C, Q, H, 8]
    rows = rows.transpose(0, 3, 4, 1, 2).reshape(bsz, h, _ROWS, t)
    return rows, jnp.exp(l[:, :, -1:])


def _dot(a, b, contract, precision, widen):
    """``a`` times ``b`` over ``contract``'s axes, float32 out.  ``widen``
    (interpret mode) multiplies the operands as float32, to the same
    products: XLA's CPU backend cannot run every bf16 product a kernel
    holds (``DotThunk``, jax 0.9)."""
    if widen:
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jax.lax.dot_general(
        a, b, ((contract[:1], contract[1:]), ((), ())),
        preferred_element_type=jnp.float32, precision=precision,
    )


def _seen(q):
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    return rows >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)


class _Head:
    """What both kernels read of head ``h`` of a block: its rows of the
    ``[heads P, .]`` arrays, its token rows (each ``[1, Q]``, tokens
    along the lanes) and ``l`` once more as a column."""

    def __init__(self, h, v_ref, lcol_scr, p):
        self.rows = pl.ds(pl.multiple_of(h * p, p), p)
        self.tile = v_ref[h]  # [8, Q]
        self.l_col = lcol_scr[h]  # [Q, 1]

    def row(self, k):
        return self.tile[k:k + 1, :]

    def decay(self, seen):
        """``exp(l_t - l_s)`` [t, s] for ``s <= t``, masked BEFORE the
        exp: above the diagonal ``l_t - l_s`` is positive."""
        return jnp.exp(jnp.where(seen, self.l_col - self.row(_L), -jnp.inf))


def _columns(l_ref, lcol_scr):
    """``l`` of a block's heads as columns ``[heads, Q, 1]``: ONE
    transpose a grid step (a head's own ``[8, Q]`` tile transposed cost
    0.32 ms a forward call: `tool/ssd_sweep.py`, my chip run, PR 36)."""
    if len(l_ref.shape) == 3:
        # A block of one head is handed its token rows [1, 8, Q] as
        # l_ref (a [1, Q] block of l does not tile): their transposed
        # row _L is the column.
        lcol_scr[0] = l_ref[0].T[:, _L:_L + 1]
        return
    lt = l_ref[...].T  # [Q, heads]
    for h in range(lcol_scr.shape[0]):
        lcol_scr[h] = lt[:, h:h + 1]


def _heads(hb, body):
    """``body(h, None)`` over a block's heads: ONE traced body, unrolled
    when the kernel is lowered so that a head's products (the MXU's) run
    under its neighbours' decays (the VPU's).  As a rolled loop a call
    at the benchmark's shape took 0.862 ms forward and 1.445 backward,
    unrolled 0.491 and 0.998 (16 heads a block; my chip run, PR 36)."""
    jax.lax.fori_loop(0, hb, body, None, unroll=True)


def _fwd_kernel(x_ref, v_ref, l_ref, whole_ref, b_ref, c_ref, y_ref,
                before_ref, h_scr, lcol_scr, xt_scr, zt_scr, yt_scr, xet_scr,
                *, hb, p, precision, widen):
    c, j = pl.program_id(1), pl.program_id(2)
    q, dtype, f32 = x_ref.shape[0], x_ref.dtype, jnp.float32
    dot = functools.partial(_dot, precision=precision, widen=widen)

    @pl.when(c == 0)
    def _():
        h_scr[j] = jnp.zeros(h_scr.shape[1:], h_scr.dtype)

    before = h_scr[j]  # H_{c-1}, [heads P, N]
    before_ref[...] = before
    bm, cm = b_ref[...], c_ref[...]
    cb = dot(cm, bm, (1, 1))  # (C_t . B_s), once a step
    zt_scr[...] = dot(before.astype(dtype), cm, (1, 1))  # H_{c-1} C_t
    # Tokens along the lanes: what scales a token is then a row, which
    # broadcasts over sublanes for nothing.
    xt_scr[...] = x_ref[...].astype(f32).T  # [heads P, Q]
    _columns(l_ref, lcol_scr)
    seen = _seen(q)

    def head(h, _):
        hd = _Head(h, v_ref, lcol_scr, p)
        m = (cb * hd.decay(seen)).astype(dtype)
        xt = xt_scr[hd.rows, :]
        xd = (xt * hd.row(_DT)).astype(dtype)  # dt_s x_s
        # The chunk's own state, decayed to the chunk's end.
        xet_scr[hd.rows, :] = (xt * hd.row(_DTW)).astype(dtype)
        yt_scr[hd.rows, :] = (
            dot(xd, m, (1, 1)) + zt_scr[hd.rows, :] * hd.row(_E)
            + xt * hd.row(_D)
        )
        h_scr[j, hd.rows, :] = h_scr[j, hd.rows, :] * whole_ref[0, j * hb + h]

    _heads(hb, head)
    y_ref[...] = yt_scr[...].T.astype(dtype)
    # H_c = exp(l_Q) H_{c-1} + S_c
    h_scr[j] += dot(xet_scr[...], bm, (1, 0))


def _bwd_kernel(x_ref, v_ref, l_ref, whole_ref, b_ref, c_ref, before_ref,
                dy_ref, dx_ref, dv_ref, dwhole_ref, db_ref, dc_ref,
                dh_scr, lcol_scr, xt_scr, zt_scr, dyt_scr, dxet_scr, dxt_scr,
                xet_scr, dzt_scr, dcb_scr,
                *, hb, p, blocks_a_group, precision, widen):
    c, j = pl.program_id(1), pl.program_id(2)
    q, dtype, f32 = x_ref.shape[0], x_ref.dtype, jnp.float32
    dot = functools.partial(_dot, precision=precision, widen=widen)

    @pl.when(c == 0)  # the LAST chunk: the grid walks them in reverse
    def _():
        dh_scr[j] = jnp.zeros(dh_scr.shape[1:], dh_scr.dtype)

    bm, cm = b_ref[...], c_ref[...]
    before = before_ref[...].astype(dtype)
    after = dh_scr[j].astype(dtype)  # dH_c
    cb = dot(cm, bm, (1, 1))
    dcb_scr[...] = jnp.zeros_like(dcb_scr)
    zt_scr[...] = dot(before, cm, (1, 1))  # H_{c-1} C_t
    dxet_scr[...] = dot(after, bm, (1, 1))  # d(dt exp(l_Q - l) x)
    xt_scr[...] = x_ref[...].astype(f32).T
    dyt_scr[...] = dy_ref[...].astype(f32).T
    _columns(l_ref, lcol_scr)
    seen = _seen(q)
    sub8 = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, 1), 0)
    over = lambda v: jnp.sum(v, axis=0, keepdims=True)  # a head's P, or t

    def head(h, _):
        hd = _Head(h, v_ref, lcol_scr, p)
        decay = hd.decay(seen)
        m = (cb * decay).astype(dtype)
        xt, dyt = xt_scr[hd.rows, :], dyt_scr[hd.rows, :]
        dy = dyt.astype(dtype)
        xd = (xt * hd.row(_DT)).astype(dtype)
        dm = dot(dy, xd, (0, 0))  # [t, s]
        dcb_scr[...] += dm * decay
        # d(l_t - l_s), zero above the diagonal; over s it sums to what
        # dy and the intra-chunk output give, tokens along the lanes
        dspan = dm * m.astype(f32)
        dl = over(dyt * dot(xd, m, (1, 1))) - over(dspan)
        dxd = dot(dy, m, (1, 0))  # [P, s]
        dxe, zt = dxet_scr[hd.rows, :], zt_scr[hd.rows, :]
        dxt_scr[hd.rows, :] = (
            dyt * hd.row(_D) + dxd * hd.row(_DT) + dxe * hd.row(_DTW)
        )
        xet_scr[hd.rows, :] = (xt * hd.row(_DTW)).astype(dtype)
        dzt_scr[hd.rows, :] = (dyt * hd.row(_E)).astype(dtype)
        # the cotangents of the head's token rows
        rows = {_L: dl, _DT: over(dxd * xt), _E: over(dyt * zt),
                _DTW: over(dxe * xt), _D: over(dyt * xt)}
        dv_ref[h] = sum(jnp.where(sub8 == k, v, 0.0) for k, v in rows.items())
        # The state's own decay: H_c = exp(l_Q) H_{c-1} + S_c.
        dh = dh_scr[j, hd.rows, :]
        dwhole_ref[0, j * hb + h] = jnp.sum(dh * before_ref[hd.rows, :])
        dh_scr[j, hd.rows, :] = dh * whole_ref[0, j * hb + h]

    _heads(hb, head)
    dx_ref[...] = dxt_scr[...].T.astype(dtype)
    dzt, dcb = dzt_scr[...], dcb_scr[...].astype(dtype)
    dc = dot(dzt, before, (0, 0)) + dot(dcb, bm, (1, 0))
    db = dot(xet_scr[...], after, (0, 0)) + dot(dcb, cm, (0, 0))
    dh_scr[j] += dot(dzt, cm, (1, 0))  # dH_{c-1}

    first = j % blocks_a_group == 0  # a group's blocks are consecutive

    @pl.when(first)
    def _():
        db_ref[...], dc_ref[...] = db, dc

    @pl.when(jnp.logical_not(first))
    def _():
        db_ref[...] += db
        dc_ref[...] += dc


def _precision(dtype):
    # float32 operands multiply in float32 (Mosaic's default is one bf16
    # pass); narrower operands are the MXU's own.
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


class _Plan(NamedTuple):
    """A call's static arguments: what the jitted wrappers and the
    differentiation rule are keyed on."""

    chunk: int
    hb: int  # heads a grid step
    p: int  # a head's width
    groups: int
    interpret: bool


def _l_rows(rows, plan):
    """What the kernels read ``l`` from: the token rows' ``l`` row, or
    with one group a head the token rows whole (:func:`_columns`)."""
    return rows if plan.groups == rows.shape[1] else rows[:, :, _L]


def _vmem_bytes(plan, heads, state, itemsize):
    """A grid step's working set and the carried states of all heads."""
    return (
        step_vmem_bytes(plan.hb, plan.p, state, plan.chunk, itemsize)
        + heads * plan.p * state * 4
    )


def _specs(x, cm, plan, reverse):
    """Grid, block specs and scratch shapes both kernels share:
    ``x``-shaped blocks ``[Q, heads P]``, token rows ``[heads, 8, Q]``
    (and ``l`` alone ``[heads, Q]``), a group's ``B``/``C`` ``[Q, N]``,
    a block's states ``[heads P, N]``; the backward kernel walks the
    chunks in reverse."""
    chunk, hb, p, groups, interpret = plan
    bsz, t, hp = x.shape
    n, chunks, blocks = cm.shape[2] // groups, t // chunk, hp // p // hb
    a_group = blocks // groups
    at = (lambda c: chunks - 1 - c) if reverse else (lambda c: c)
    return dict(
        grid=(bsz, chunks, blocks),
        a_group=a_group,
        states=jax.ShapeDtypeStruct((bsz, chunks, hp, n), jnp.float32),
        wide=pl.BlockSpec((None, chunk, hb * p), lambda b, c, j: (b, at(c), j)),
        rows=pl.BlockSpec(
            (None, hb, _ROWS, chunk), lambda b, c, j: (b, j, 0, at(c))
        ),
        l=pl.BlockSpec(
            (None, hb, _ROWS, chunk), lambda b, c, j: (b, j, 0, at(c))
        ) if groups == hp // p else pl.BlockSpec(
            (None, hb, chunk), lambda b, c, j: (b, j, at(c))
        ),
        group=pl.BlockSpec(
            (None, chunk, n), lambda b, c, j: (b, at(c), j // a_group)
        ),
        # exp(l_Q) a head, scalars in SMEM
        whole=pl.BlockSpec(
            (None, None, 1, hp // p), lambda b, c, j: (b, at(c), 0, 0),
            memory_space=pltpu.SMEM,
        ),
        state=pl.BlockSpec(
            (None, None, hb * p, n), lambda b, c, j: (b, at(c), j, 0)
        ),
        carried=pltpu.VMEM((blocks, hb * p, n), jnp.float32),
        columns=pltpu.VMEM((hb, chunk, 1), jnp.float32),
        # tokens along the lanes: [heads P, Q]
        across=lambda dtype: pltpu.VMEM((hb * p, chunk), dtype),
        # what `pallas_call` takes beside: the kernel's static arguments
        # are the caller's to bind
        call=dict(interpret=True) if interpret else dict(
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",) * 3,
                vmem_limit_bytes=min(100 * 2 ** 20, max(32 * 2 ** 20, 2 * (
                    _vmem_bytes(plan, hp // p, n, x.dtype.itemsize)
                ))),
            )
        ),
        kernel=dict(
            hb=hb, p=p, precision=_precision(x.dtype), widen=interpret
        ),
    )


@functools.partial(jax.jit, static_argnames="plan")
def _forward(x, rows, whole, bm, cm, *, plan):
    """``y`` [B, T, H P] and the carried states ``H_{c-1}`` [B, T/Q,
    H P, N] float32 of ``x`` [B, T, H P], token rows [B, H, 8, T],
    ``exp(l_Q)`` [B, T/Q, 1, H] and ``B``, ``C`` [B, T, G N]; ``T`` a
    multiple of the chunk."""
    s = _specs(x, cm, plan, reverse=False)
    f32 = jnp.float32
    with jax.named_scope("ssd.fwd"):
        return pl.pallas_call(
            functools.partial(_fwd_kernel, **s["kernel"]),
            grid=s["grid"],
            in_specs=[s["wide"], s["rows"], s["l"], s["whole"], s["group"],
                      s["group"]],
            out_specs=[s["wide"], s["state"]],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), s["states"]],
            scratch_shapes=[
                s["carried"], s["columns"], s["across"](f32), s["across"](f32),
                s["across"](f32), s["across"](x.dtype),
            ],
            **s["call"],
        )(x, rows, _l_rows(rows, plan), whole, bm, cm)


@functools.partial(jax.jit, static_argnames="plan")
def _backward(x, rows, whole, bm, cm, before, dy, *, plan):
    """``dx``, the cotangents of the token rows and of ``exp(l_Q)``,
    ``dB`` and ``dC`` (float32, summed over a group's heads)."""
    s = _specs(x, cm, plan, reverse=True)
    f32 = jnp.float32
    with jax.named_scope("ssd.bwd"):
        return pl.pallas_call(
            functools.partial(
                _bwd_kernel, blocks_a_group=s["a_group"], **s["kernel"]
            ),
            grid=s["grid"],
            in_specs=[s["wide"], s["rows"], s["l"], s["whole"], s["group"],
                      s["group"], s["state"], s["wide"]],
            out_specs=[s["wide"], s["rows"], s["whole"], s["group"],
                       s["group"]],
            out_shape=[
                jax.ShapeDtypeStruct(x.shape, x.dtype),
                jax.ShapeDtypeStruct(rows.shape, f32),
                jax.ShapeDtypeStruct(whole.shape, f32),
                jax.ShapeDtypeStruct(bm.shape, f32),
                jax.ShapeDtypeStruct(cm.shape, f32),
            ],
            scratch_shapes=[
                s["carried"], s["columns"],
                *[s["across"](f32)] * 5, *[s["across"](x.dtype)] * 2,
                pltpu.VMEM((plan.chunk, plan.chunk), f32),
            ],
            **s["call"],
        )(x, rows, _l_rows(rows, plan), whole, bm, cm, before, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan(x, rows, whole, bm, cm, plan):
    return _forward(x, rows, whole, bm, cm, plan=plan)[0]


def _scan_fwd(x, rows, whole, bm, cm, plan):
    y, before = _forward(x, rows, whole, bm, cm, plan=plan)
    return y, (x, rows, whole, bm, cm, before)


def _scan_bwd(plan, residuals, dy):
    x, rows, whole, bm, cm, before = residuals
    # The rule is traced outside the caller's scope: name it again, so
    # the device time of the backward kernel is the scan's.
    with jax.named_scope("ssm.scan"):
        dx, drows, dwhole, db, dc = _backward(
            x, rows, whole, bm, cm, before, dy, plan=plan
        )
        return dx, drows, dwhole, db.astype(bm.dtype), dc.astype(cm.dtype)


_scan.defvjp(_scan_fwd, _scan_bwd)


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
    chunk = min(int(chunk), t)
    chunks = -(-t // chunk)
    dtype, f32 = x.dtype, jnp.float32
    interpret = _flash._interpret_default()
    if not interpret:
        _check_tiling(chunk, p, n, g, dtype.itemsize, h)
    plan = _Plan(
        chunk, head_block(h, g, p, n, chunk, dtype.itemsize, interpret), p, g,
        interpret,
    )
    if telemetry.armed():
        # One record a call traced while the recorder is armed: what the
        # scan of this call computes and how the kernels are laid out,
        # from its static arguments alone.
        states = bsz * chunks * h * p * n * 4
        telemetry.emit(
            "ssm.scan",
            detail=dict(
                batch=bsz, tokens=t, chunk=chunk, chunks=chunks, heads=h,
                head_dim=p, state=n, groups=g,
                flops_forward=scan_flops(bsz * t, h, p, n, g, chunk),
                bytes_forward=scan_bytes(bsz, t, h, p, n, g, dtype.itemsize),
                # the float32 states the chunks carry
                state_bytes=states,
                # the largest array in HBM: the carried states (the
                # masked decay, `chunk` times `chunk` a head, is VMEM's)
                working_set_bytes=states,
                head_block=plan.hb, grid=(bsz, chunks, h // plan.hb),
                # a grid step's working set (the backward kernel's)
                vmem_bytes=_vmem_bytes(plan, h, n, dtype.itemsize),
                # what the backward pass is handed beside the call's
                # inputs: the carried states and the token rows
                residual_bytes=states + bsz * h * _ROWS * chunks * chunk * 4,
            ),
        )
    with jax.named_scope("ssm.scan"):
        pad = chunks * chunk - t
        padded = lambda v: jnp.pad(
            v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2)
        ).reshape(bsz, chunks * chunk, -1)
        rows, whole = _token_rows(
            padded(dt.astype(f32)), A.astype(f32), D.astype(f32), chunk
        )
        y = _scan(
            padded(x), rows, whole, padded(B.astype(dtype)),
            padded(C.astype(dtype)), plan,
        )
        return y.reshape(bsz, chunks * chunk, h, p)[:, :t]
