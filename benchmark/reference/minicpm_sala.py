"""Plain reference of the ``minicpm_sala`` decoder (openbmb MiniCPM-SALA:
InfLLM-v2 block-sparse attention layers, ``minicpm4``, among decayed
linear-attention layers, ``lightning-attn``; arXiv:2509.24663, the
MiniCPM4 report arXiv:2506.07900 section 2.1, Lightning Attention-2
arXiv:2401.04658, MiniMax-01 arXiv:2501.08313) as its first layers run
here: forward and loss, and gradients by ``jax.grad``.

Straightforward ``jax.numpy`` in float32; callers wrap it in
``jax.default_matmul_precision("highest")``.  No kernel and no chunked
form: the linear attention's recurrence TOKEN BY TOKEN (a ``lax.scan``
over the sequence), the sparse attention as scores over EVERY key under
an explicit mask of the selected blocks, in query blocks.  Imports
nothing from ``rayfed_tpu``; takes the system's parameter tree (a list
of layer dicts, any float dtype) so both sides read the same weights,
and upcasts a layer's weights where it uses them.

With ``x`` the residual stream ``[T, D]`` and RMS norms (eps 1e-6):

- ``x_0 = scale_emb E[ids]`` (12).
- Layer ``l``: ``x <- x + r mixer(norm(x))``, ``x <- x + r swiglu(norm'(x))``
  with ``r = scale_depth / sqrt(mup_denominator)`` (1.4 / sqrt(32)).
- After the last layer ``logits = norm(x) W_head / (hidden /
  dim_model_base)`` (4096 / 256 = 16; an untied head), mean next-token
  cross entropy.
- **minicpm4** (sparse): ``q = rms_h(y W_q)`` (``H`` heads), ``k =
  rms_h(y W_k)``, ``v = y W_v`` (``KV`` heads), no position embedding.
  Up to ``dense_len`` tokens: causal softmax attention, scale ``d ** -0.5``.
  Beyond: for K/V head ``g``, compressed keys ``K~_j = mean(k[s j : s j +
  w])`` (``w`` 32, ``s`` 16); for query ``t``, ``p_{t,j} = sum over the
  query heads of g of softmax_j(q_{t,h} . K~_j / sqrt(d))`` over the
  windows that END at or before ``t``; block ``m`` (tokens ``b m .. b m +
  b - 1``, ``b`` 64) scores the maximum of ``p_{t,j}`` over the windows
  that overlap it; ``S_t`` is the first ``init_blocks`` blocks, every
  block holding one of the last ``window_size`` tokens up to ``t``, and the
  highest-scoring causal blocks left, ``topk`` blocks in all (the forced
  ones count inside the ``topk``); ``o_{t,h}`` is softmax attention over
  the keys ``i <= t`` of the blocks of ``S_t``.  Output ``(o *
  sigmoid(y W_z)) W_o``.
- **lightning-attn**: ``q = rope(rms_h(y W_q))``, ``k = rope(rms_h(y
  W_k))``, ``v = y W_v``, ``H`` heads each, rotary angles ``t theta ** (-2i
  / d)`` over the whole head (theta 10,000) on the pairs ``(2i, 2i +
  1)``; per head ``S_t = lambda_h S_{t-1} + k_t v_t^T``, ``o_t = (q_t /
  sqrt(d))^T S_t``, with ``lambda_h = exp(-2 ** (-8 (h + 1) / H) (1 - l /
  (depth - 1) + 1e-5))`` (MiniMax-01's slopes; ``depth`` the published
  32); output ``(rms_h(o) * sigmoid(y W_z)) W_o``, the norm over each
  head's width with a weight a channel.

Departures from the published code: the rotary pairs are ``(2i, 2i +
1)`` where the published modelling code may rotate halves ``(i, i + d /
2)``: the same rotation on permuted columns of random ``W_q`` and
``W_k``.  The FFN's gate and up matrices are two.

``omit`` removes or breaks one piece of the mathematics; the tests use
it to show that the comparison notices each: ``decay`` (every lambda
1), ``rope``, ``output_norm``,
``gate``, ``qk_norm``, ``local_blocks`` (the local window not forced),
``init_block``, ``select_sum`` (block scores from one query head, not
the group's sum), ``residual_scale``, ``scale_emb``, ``logit_scale``.
``lightning_scale`` (no ``1 / sqrt(d)`` on ``q``) is no piece the
logits can show: the output's RMS norm takes any constant factor of
``o`` away but for its ``eps``.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from benchmark.reference.afmoe import F32, _rms, _weight
from benchmark.reference.kimi_k2 import _by_rows

PIECES = ("decay", "rope", "output_norm", "gate",
          "qk_norm", "local_blocks", "init_block", "select_sum",
          "residual_scale", "scale_emb", "logit_scale")


def _round(v, round_to):
    """``v`` rounded to ``round_to`` in the forward pass; its cotangent
    passes unrounded (rounded to e4m3, a mean loss's cotangents fall
    below the type's least value and every adapter's gradient reads
    zero)."""
    if round_to is None:
        return v
    return v + jax.lax.stop_gradient(v.astype(round_to).astype(F32) - v)


def _mm(a, b, round_to=None):
    """``a @ b``, the operands :func:`_round`-ed."""
    return _round(a, round_to) @ _round(b, round_to)


def _swiglu(m, w_gate, w_up, w_down, round_to=None):
    h = jax.nn.silu(_mm(m, w_gate, round_to)) * _mm(m, w_up, round_to)
    return _mm(h, w_down, round_to)


def _head_norm(x, w, eps):
    """RMS norm over the last dim of ``x`` [T, heads, d] with ``w``
    broadcast over it."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


# -- minicpm4: InfLLM-v2 ----------------------------------------------------


def compressed_keys(k, kernel, stride):
    """``[windows, KV, d]``: the mean of ``k[s j : s j + w]`` for every
    window inside the sequence, gathered window by window."""
    t = k.shape[0]
    n = (t - kernel) // stride + 1
    at = np.arange(n)[:, None] * stride + np.arange(kernel)[None, :]
    return jnp.mean(k[at], axis=1)


def block_windows(nblocks, windows, kernel, stride, block):
    """Per block the first and last window that overlaps it (numpy):
    window ``j`` holds ``[s j, s j + w)``, block ``m`` ``[b m, b m + b)``."""
    j = np.arange(windows)
    lo, hi = [], []
    for m in range(nblocks):
        over = j[(j * stride < (m + 1) * block) & (j * stride + kernel > m * block)]
        lo.append(over.min() if over.size else 0)
        hi.append(over.max() if over.size else -1)
    return np.asarray(lo), np.asarray(hi)


def select(q, k, *, kernel_size, kernel_stride, block_size, topk,
           init_blocks, window_size, query_block=256, omit=(),
           round_to=None):
    """``[KV, T, topk]`` int32, the blocks each query of each K/V head
    selects (-1 where fewer causal blocks exist); ``q`` [T, H, d], ``k``
    [T, KV, d]."""
    t, h, d = q.shape
    kv = k.shape[1]
    kc = _round(compressed_keys(k, kernel_size, kernel_stride), round_to)
    windows = kc.shape[0]
    nblocks = -(-t // block_size)
    lo, hi = block_windows(nblocks, windows, kernel_size, kernel_stride,
                           block_size)
    width = int((hi - lo).max()) + 1
    at = np.minimum(lo[:, None] + np.arange(width)[None, :], windows - 1)
    inside = (lo[:, None] + np.arange(width)[None, :]) <= hi[:, None]
    ends = np.arange(windows) * kernel_stride + kernel_size - 1
    qb_ = min(query_block, t)
    assert t % qb_ == 0, (t, qb_)

    def one(i):
        pos = i * qb_ + jnp.arange(qb_)
        qb = _round(jax.lax.dynamic_slice_in_dim(q, i * qb_, qb_, 0), round_to)
        qb = qb.reshape(qb_, kv, h // kv, d)
        s = jnp.einsum("qghd,jgd->gqhj", qb, kc) * d ** -0.5
        valid = jnp.asarray(ends)[None, :] <= pos[:, None]  # [Q, windows]
        s = jnp.where(valid[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        p = jnp.where(valid[None, :, None], p, 0.0)
        p = jnp.nan_to_num(p)  # a query before the first window's end
        p = p[:, :, 0] if "select_sum" in omit else p.sum(axis=2)  # [KV,Q,j]
        score = jnp.max(jnp.where(inside, p[..., at], 0.0), axis=-1)  # [KV,Q,M]
        m = jnp.arange(nblocks)[None, :]
        own = (pos // block_size)[:, None]
        causal = m <= own
        forced = jnp.zeros_like(causal)
        if "init_block" not in omit:
            forced |= m < init_blocks
        if "local_blocks" not in omit:
            forced |= m >= (jnp.maximum(pos - window_size + 1, 0)
                            // block_size)[:, None]
        score = jnp.where(causal & forced, jnp.inf, score)
        score = jnp.where(causal, score, -jnp.inf)
        vals, idx = jax.lax.top_k(score, min(topk, nblocks))
        return jnp.where(vals > -jnp.inf, idx, -1)

    out = jax.lax.map(one, jnp.arange(t // qb_))  # [blocks, KV, Q, topk]
    return out.transpose(1, 0, 2, 3).reshape(kv, t, -1).astype(jnp.int32)


def selection_agreement(got, want):
    """The share of the reference's (query, K/V head, block) choices
    that the system made too: ``|S_got & S_want| / |S_want|`` over all
    of them (``[KV, T, topk]`` each, -1 for none)."""
    got, want = np.asarray(got), np.asarray(want)
    nb = int(max(got.max(), want.max())) + 2

    def hit(s):  # [T, topk] -> [T, blocks + 1], column 0 the -1s
        out = np.zeros((s.shape[0], nb), bool)
        np.put_along_axis(out, s + 1, True, axis=1)
        return out[:, 1:]

    both = wanted = 0
    for g in range(got.shape[0]):
        a, b = hit(got[g]), hit(want[g])
        both += int((a & b).sum())
        wanted += int(b.sum())
    return both / max(wanted, 1)


def sparse_attention(q, k, v, selected, *, block_size, query_block=256,
                     round_to=None, remat=False):
    """``o`` [T, H, d]: each query over the keys ``i <= t`` of its K/V
    head's selected blocks (``selected`` [KV, T, topk], -1 for none);
    scores over every key, masked, in query blocks."""
    t, h, d = q.shape
    kv = k.shape[1]
    nblocks = -(-t // block_size)
    kr, vr = (jnp.repeat(a, h // kv, axis=1) for a in (k, v))
    key_block = jnp.arange(t) // block_size
    qb_ = min(query_block, t)

    def one(i):
        pos = i * qb_ + jnp.arange(qb_)
        qb = jax.lax.dynamic_slice_in_dim(q, i * qb_, qb_, 0)
        sel = jax.lax.dynamic_slice_in_dim(selected, i * qb_, qb_, 1)
        chosen = (sel[..., None] == jnp.arange(nblocks)).any(-2)  # [KV,Q,M]
        allowed = chosen[..., key_block] & (
            jnp.arange(t)[None, None, :] <= pos[None, :, None]
        )
        allowed = jnp.repeat(allowed, h // kv, axis=0)  # [H, Q, T]
        s = jnp.einsum("qhd,khd->hqk", _round(qb, round_to),
                       _round(kr, round_to)) * d ** -0.5
        p = jax.nn.softmax(jnp.where(allowed, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", _round(p, round_to),
                          _round(vr, round_to))

    if remat:
        one = jax.checkpoint(one)
    return jax.lax.map(one, jnp.arange(t // qb_)).reshape(t, h, d)


def sparse_mixer(y, lp, lora, *, num_heads, num_kv_heads, head_dim, sparse,
                 rms_eps, selected=None, omit=(), round_to=None, remat=False):
    """The minicpm4 mixer on the normed stream ``y`` [T, D] -> (``[T,
    D]``, its OWN selection, or None where it attends densely).  A
    ``selected`` given is attended over in place of its own."""
    t, h, kv, d = y.shape[0], num_heads, num_kv_heads, head_dim
    wt = lambda name: _weight(lp[name], lora.get(name))
    f32 = lambda a: jnp.asarray(a, F32)
    q = _mm(y, wt("wq"), round_to).reshape(t, h, d)
    k = _mm(y, wt("wk"), round_to).reshape(t, kv, d)
    v = _mm(y, wt("wv"), round_to).reshape(t, kv, d)
    if "qk_norm" not in omit:
        q = _head_norm(q, f32(lp["q_norm"]), rms_eps)
        k = _head_norm(k, f32(lp["k_norm"]), rms_eps)
    sizes = dict(sparse)
    dense_len, block = sizes.pop("dense_len"), sizes["block_size"]
    if t <= dense_len:
        o = sparse_attention(
            q, k, v, jnp.broadcast_to(
                jnp.arange(-(-t // block), dtype=jnp.int32), (kv, t, -(-t // block))
            ), block_size=block, round_to=round_to, remat=remat,
        )
        selected = None
    else:
        own = select(q, k, omit=omit, round_to=round_to, **sizes)
        o = sparse_attention(q, k, v, own if selected is None else selected,
                             block_size=block, round_to=round_to, remat=remat)
        selected = own
    o = o.reshape(t, h * d)
    if "gate" not in omit:
        o = o * jax.nn.sigmoid(_mm(y, wt("wz"), round_to))
    return _mm(o, wt("wo"), round_to), selected


# -- lightning-attn ----------------------------------------------------------


def rope(x, theta):
    """``x`` [T, heads, d] rotated on the pairs ``(2i, 2i + 1)`` by
    ``t theta ** (-2i / d)``."""
    t, _, d = x.shape
    inv = theta ** (-np.arange(0, d, 2) / d)
    ang = jnp.arange(t, dtype=F32)[:, None] * jnp.asarray(inv, F32)[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * c - b * s, b * c + a * s], axis=-1).reshape(x.shape)


def decays(layer: int, heads: int, depth: int):
    """``lambda_h`` [heads] of layer ``layer`` (MiniMax-01's slopes)."""
    slopes = 2.0 ** (-8.0 * (np.arange(heads) + 1) / heads)
    return np.exp(-slopes * (1.0 - layer / (depth - 1) + 1e-5))


def recurrence(q, k, v, lam, *, block=128, remat=False):
    """``o`` [T, H, d]: ``S_t = lam S_{t-1} + k_t v_t^T``, ``o_t = q_t^T
    S_t``, token by token.  ``remat``: the scan in blocks of ``block``
    tokens, each run again in the backward pass (24,576 states of 2 MB do
    not fit): memory, not mathematics."""
    t, h, d = q.shape

    def step(state, inputs):
        q_t, k_t, v_t = inputs
        state = lam[:, None, None] * state + k_t[:, :, None] * v_t[:, None, :]
        return state, jnp.einsum("hn,hnp->hp", q_t, state)

    state = jnp.zeros((h, d, v.shape[-1]), F32)
    inputs = (q, k, v)
    if not remat or t % block:
        return jax.lax.scan(step, state, inputs)[1]
    blocks = jax.tree_util.tree_map(
        lambda a: a.reshape(t // block, block, *a.shape[1:]), inputs
    )
    _, o = jax.lax.scan(
        jax.checkpoint(lambda s, blk: jax.lax.scan(step, s, blk)), state,
        blocks,
    )
    return o.reshape(t, h, -1)


def lightning_mixer(y, lp, lora, *, index, num_heads, head_dim, depth,
                    rope_theta, rms_eps, omit=(), round_to=None, remat=False):
    """The lightning-attn mixer of layer ``index`` on the normed stream
    ``y`` [T, D]; ``remat``: the recurrence's blocks are run again in
    the backward pass."""
    t, h, d = y.shape[0], num_heads, head_dim
    wt = lambda name: _weight(lp[name], lora.get(name))
    f32 = lambda a: jnp.asarray(a, F32)
    q, k, v = (_mm(y, wt(n), round_to).reshape(t, h, d)
               for n in ("wq", "wk", "wv"))
    if "qk_norm" not in omit:
        q = _head_norm(q, f32(lp["q_norm"]), rms_eps)
        k = _head_norm(k, f32(lp["k_norm"]), rms_eps)
    if "rope" not in omit:
        q, k = rope(q, rope_theta), rope(k, rope_theta)
    q = q * d ** -0.5
    lam = jnp.ones((h,), F32) if "decay" in omit else jnp.asarray(
        decays(index, h, depth), F32
    )
    q, k, v = (_round(a, round_to) for a in (q, k, v))
    o = recurrence(q, k, v, lam, remat=remat)
    if "output_norm" not in omit:
        o = _head_norm(o, f32(lp["o_norm"]).reshape(h, d), rms_eps)
    o = o.reshape(t, h * d)
    if "gate" not in omit:
        o = o * jax.nn.sigmoid(_mm(y, wt("wz"), round_to))
    return _mm(o, wt("wo"), round_to)


# -- the block ----------------------------------------------------------------


def embed(params, ids, *, scale_emb, omit=()):
    by = 1.0 if "scale_emb" in omit else scale_emb
    return jnp.asarray(params["embed"][ids], F32) * by


def layer(x, lp, *, kind: str, index: int, residual_scale, rms_eps, attn,
          sparse, lightning, lora=None, selected=None, block=512, omit=(),
          round_to=None, remat=False):
    """One layer on the stream ``x`` [T, D] -> (``x``, the sparse layer's
    own selection or None); ``kind`` ``"minicpm4"`` or ``"lightning-attn"``
    (the configuration's ``mixer_types``), ``index`` the layer's
    published index."""
    f32 = lambda a: jnp.asarray(a, F32)
    ll = lora or {}
    t = x.shape[0]
    block = min(block, t)
    by = 1.0 if "residual_scale" in omit else residual_scale
    y = _rms(x, f32(lp["attn_norm"]), rms_eps)
    common = dict(omit=omit, round_to=round_to, rms_eps=rms_eps)
    if kind == "minicpm4":
        o, selected = sparse_mixer(y, lp, ll, sparse=sparse, selected=selected,
                                   remat=remat, **attn, **common)
    else:
        assert kind == "lightning-attn", kind
        o = lightning_mixer(y, lp, ll, index=index, num_heads=attn["num_heads"],
                            head_dim=attn["head_dim"], remat=remat,
                            **lightning, **common)
        selected = None
    x = x + by * o
    m = _rms(x, f32(lp["mlp_norm"]), rms_eps)
    mats = [_weight(lp[n], ll.get(n)) for n in ("w_gate", "w_up", "w_down")]
    ffn = lambda rows: _swiglu(rows, *mats, round_to)
    return x + by * _by_rows(ffn, m, block, remat and t % block == 0), selected


def logits(x, params, *, rms_eps, logit_scale, last=None, omit=(),
           round_to=None):
    """``norm(x) W_head * logit_scale`` of the last ``last`` positions."""
    if last is not None:
        x = x[-last:]
    x = _rms(x, jnp.asarray(params["final_norm"], F32), rms_eps)
    by = 1.0 if "logit_scale" in omit else logit_scale
    return _mm(x, jnp.asarray(params["lm_head"], F32), round_to) * by


def head_loss(x, params, ids, *, block=512, **kw):
    """Mean next-token cross entropy of one sequence, the logits a block
    of rows at a time (memory, not mathematics)."""
    t = x.shape[0]
    block = min(block, t)
    if t % block:
        block = t
    targets = jnp.roll(ids, -1)

    def rows(args):
        xb, tb = args
        logp = jax.nn.log_softmax(logits(xb, params, **kw), axis=-1)
        return -jnp.take_along_axis(logp, tb[:, None], axis=-1)[:, 0]

    nll = jax.lax.map(
        jax.checkpoint(rows),
        (x.reshape(t // block, block, -1), targets.reshape(t // block, block)),
    ).reshape(t)
    return jnp.sum(nll[:-1]) / (t - 1)


def hidden(params, ids, *, mixer_types, scale_emb, lora=None, selected=None,
           omit=(), round_to=None, remat=False, **widths):
    """(the stream after the last layer of ``params`` for ONE sequence
    ``ids`` [T], every sparse layer's own selection); ``selected``: a
    selection a sparse layer, in order, to attend over instead."""
    assert set(omit) <= set(PIECES), omit
    assert len(mixer_types) == len(params["layers"])
    lora_layers = (lora or {}).get("layers", {})
    x = embed(params, ids, scale_emb=scale_emb, omit=omit)
    given = list(selected or [])
    chosen = []
    for i, (kind, lp) in enumerate(zip(mixer_types, params["layers"])):
        sel = given[len(chosen)] if given and kind == "minicpm4" else None

        def one(x, lp, ll, sel, kind=kind, i=i):
            return layer(x, lp, kind=kind, index=i, lora=ll, selected=sel,
                         omit=omit, round_to=round_to, remat=remat, **widths)

        if remat:
            one = jax.checkpoint(one)
        x, sel = one(x, lp, lora_layers.get(str(i), {}), sel)
        if kind == "minicpm4":
            chosen.append(sel)
    return x, chosen


def forward(params, ids, *, logit_scale, last=None, **kw):
    """(logits ``[last, vocab]``, the selections)."""
    x, chosen = hidden(params, ids, **kw)
    return logits(
        x, params, rms_eps=kw["rms_eps"], logit_scale=logit_scale, last=last,
        omit=kw.get("omit", ()), round_to=kw.get("round_to"),
    ), chosen


def loss(params, ids, *, logit_scale, **kw):
    """Mean next-token cross entropy of one sequence, float32."""
    x, _ = hidden(params, ids, **kw)
    return head_loss(
        x, params, ids, rms_eps=kw["rms_eps"], logit_scale=logit_scale,
        omit=kw.get("omit", ()), round_to=kw.get("round_to"),
    )


def lora_gradients(params, lora, ids, **kw):
    """(loss, d loss / d every adapter leaf) by ``jax.grad``."""
    return jax.value_and_grad(
        lambda l: loss(params, ids, lora=l, **kw)
    )(lora)
