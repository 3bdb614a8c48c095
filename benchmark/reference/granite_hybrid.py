"""Plain reference of the ``granitemoehybrid`` decoder with Mamba-2
mixers (ibm-granite granite-4.0-h; ``transformers``
``models/granitemoehybrid/modeling_granitemoehybrid.py``:
``GraniteMoeHybridDecoderLayer``, ``GraniteMoeHybridMambaLayer``,
``GraniteMoeHybridRMSNormGated``, ``GraniteMoeHybridAttention``,
``GraniteMoeHybridMLP``; the state-space recurrence of arXiv:2405.21060)
as the first layers of it run here: forward, loss, and gradients by
``jax.grad``.

Straightforward ``jax.numpy`` in float32; callers wrap it in
``jax.default_matmul_precision("highest")``.  No kernel and no chunked
form: THE RECURRENCE TOKEN BY TOKEN (a ``lax.scan`` over the sequence),
the attention in query blocks under an explicit mask.  Imports nothing
from ``rayfed_tpu``; takes the system's parameter tree (a list of layer
dicts, any float dtype) so both sides read the same weights, and upcasts
a layer's weights where it uses them (``embed`` / ``layer`` /
``head_loss`` can be called one at a time, so that one layer's float32
copy lives at once).

With ``x`` the residual stream ``[T, D]``, RMS norms with ``eps``:

- ``x_0 = embedding_multiplier E[ids]`` (12).
- Layer: ``y = norm(x)``; ``x <- x + residual_multiplier mixer(y)``
  (0.22); ``y = norm'(x)``; ``g = y W_gate``, ``u = y W_up``; ``x <- x +
  residual_multiplier (silu(g) * u) W_down``.
- After the last layer: ``norm``, ``logits = x E^T / logits_scaling``
  (the head is tied to the embedding; 8), mean next-token cross entropy.
- **Attention** layer: ``q = y W_q`` (``H`` heads), ``k = y W_k``, ``v =
  y W_v`` (``KV`` heads, each serving ``H / KV`` query heads), NO
  position embedding, scores ``q . k x attention_multiplier`` (1/64, NOT
  ``head_dim ** -0.5``), causal softmax, ``o W_o``; no bias, no head
  norm, no gate.
- **Mamba-2** layer: ``H`` heads of width ``P``, state ``N``, ``G``
  groups, ``d_inner = H P``.  ``[z | xBC | dt] = y W_in`` (``d_inner |
  d_inner + 2 G N | H``: gate, convolved part, time step).  ``xBC'_t[c] =
  silu(b[c] + sum_{j < K} w[c, j] xBC_{t - (K - 1) + j}[c])``, zeros before
  position 0 (depthwise, causal).  ``[x | B | C] = xBC'_t`` (``d_inner``
  as ``[H, P]`` | ``G N`` | ``G N``).  ``dt_t = softplus(dt_t + dt_bias)``
  a head (``time_step_limit`` is ``(0, inf)``: no clamp); ``A =
  -exp(A_log)`` a head; ``a_t = exp(dt_t A)``.  **The recurrence:** ``h_t
  = a_t h_{t-1} + dt_t x_t (x) B_t`` (``h`` is ``[H, P, N]``, ``h_{-1} =
  0``); ``y_t = h_t C_t + D x_t``.  Gated norm: ``u_t = y_t * silu(z_t)``;
  ``o_t = u_t / sqrt(mean(u_t^2) + eps) * w`` (one group over all of
  ``d_inner``).  ``mixer(y) = o W_out``.

Departures from the published code: the FFN's gate and up matrices are
two (``w_gate``, ``w_up``) where the published module fuses them in
``input_linear`` (gate first): the same product, and an adapter a
matrix.  ``B`` and ``C`` of a group serve its ``H / G`` heads.

``omit`` removes or breaks one piece of the mathematics; the tests use
it to show that the comparison notices each: ``carried_state`` (the
state dropped every ``chunk`` tokens, a chunked form that forgets its
boundary), ``conv_bias``, ``conv_shift`` (the taps one token late: the
convolution sees ``t + 1``), ``skip_D``, ``gate_in_norm`` (the gate
applied after the norm), ``score_scale`` (``head_dim ** -0.5`` for
1/64), ``residual_multiplier``, ``embedding_multiplier``,
``logits_scaling``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.afmoe import (  # noqa: F401  (re-exported)
    F32,
    _mm,
    _rms,
    _swiglu,
    _weight,
)
from benchmark.reference.kimi_k2 import _by_rows, _causal_attention

PIECES = ("carried_state", "conv_bias", "conv_shift", "skip_D",
          "gate_in_norm", "score_scale", "residual_multiplier",
          "embedding_multiplier", "logits_scaling")


def recurrence(x, dt, a, b, c, d, *, reset_every=None, block=128,
               remat=False):
    """``y`` [T, H, P] of the recurrence above, token by token.  ``x``
    [T, H, P]; ``dt`` [T, H] (after softplus); ``a`` [H] negative; ``b``,
    ``c`` [T, H, N] (a group's repeated to its heads); ``d`` [H].
    ``reset_every``: the state set to zero before every such token (a
    fault, for the tests).  ``remat``: the scan in blocks of ``block``
    tokens, each run again in the backward pass (8,192 states of 2 MB do
    not fit): memory, not mathematics."""
    t, h, p = x.shape
    n = b.shape[-1]

    def step(state, inputs):
        x_t, dt_t, b_t, c_t, keep = inputs
        state = jnp.exp(dt_t * a)[:, None, None] * state * keep + (
            (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        )
        return state, jnp.einsum("hpn,hn->hp", state, c_t) + d[:, None] * x_t

    keep = jnp.ones((t,), F32)
    if reset_every:
        keep = (jnp.arange(t) % reset_every != 0).astype(F32)
    inputs = (x, dt, b, c, keep)
    state = jnp.zeros((h, p, n), F32)
    if not remat or t % block:
        return jax.lax.scan(step, state, inputs)[1]
    blocks = jax.tree_util.tree_map(
        lambda v: v.reshape(t // block, block, *v.shape[1:]), inputs
    )
    _, y = jax.lax.scan(
        jax.checkpoint(lambda s, blk: jax.lax.scan(step, s, blk)), state,
        blocks,
    )
    return y.reshape(t, h, p)


def mamba_mixer(y, lp, lora, *, heads, head_dim, state, groups, conv_width,
                chunk, rms_eps, omit=(), round_to=None, remat=False):
    """The Mamba-2 mixer on the normed stream ``y`` [T, D]."""
    f32 = lambda v: jnp.asarray(v, F32)
    t = y.shape[0]
    d_inner, gn, k = heads * head_dim, groups * state, conv_width
    proj = _mm(y, _weight(lp["w_in"], lora.get("w_in")), round_to)
    z = proj[:, :d_inner]
    xbc = proj[:, d_inner: 2 * d_inner + 2 * gn]
    dt = jax.nn.softplus(proj[:, 2 * d_inner + 2 * gn:] + f32(lp["dt_bias"]))
    # the depthwise causal convolution: tap j reads token t - (K - 1) + j
    late = 1 if "conv_shift" in omit else 0
    padded = jnp.pad(xbc, [(k - 1 - late, late), (0, 0)])
    w = f32(lp["conv_w"])
    conv = sum(padded[j: j + t] * w[:, j] for j in range(k))
    if "conv_bias" not in omit:
        conv = conv + f32(lp["conv_b"])
    xbc = jax.nn.silu(conv)
    x = xbc[:, :d_inner].reshape(t, heads, head_dim)
    rep = lambda v: jnp.repeat(
        v.reshape(t, groups, state), heads // groups, axis=1
    )
    b, c = rep(xbc[:, d_inner: d_inner + gn]), rep(xbc[:, d_inner + gn:])
    if round_to is not None:  # the operands of the scan's products
        x, b, c = (v.astype(round_to).astype(F32) for v in (x, b, c))
    d = f32(lp["D"])
    s = recurrence(
        x, dt, -jnp.exp(f32(lp["A_log"])), b, c,
        jnp.zeros_like(d) if "skip_D" in omit else d,
        reset_every=chunk if "carried_state" in omit else None, remat=remat,
    ).reshape(t, d_inner)
    gate = jax.nn.silu(z)
    if "gate_in_norm" in omit:
        o = _rms(s, f32(lp["ssm_norm"]), rms_eps) * gate
    else:
        o = _rms(s * gate, f32(lp["ssm_norm"]), rms_eps)
    return _mm(o, _weight(lp["w_out"], lora.get("w_out")), round_to)


def attention_mixer(y, lp, lora, *, num_heads, num_kv_heads, attn_head_dim,
                    attention_multiplier, block, omit=(), round_to=None,
                    remat=False):
    """Causal attention without positions on the normed stream ``y``."""
    t, h, kv, dh = y.shape[0], num_heads, num_kv_heads, attn_head_dim
    wt = lambda name: _weight(lp[name], lora.get(name))
    q = _mm(y, wt("wq"), round_to).reshape(t, h, dh)
    k = _mm(y, wt("wk"), round_to).reshape(t, kv, dh)
    v = _mm(y, wt("wv"), round_to).reshape(t, kv, dh)
    k, v = (jnp.repeat(a, h // kv, axis=1) for a in (k, v))
    scale = dh ** -0.5 if "score_scale" in omit else attention_multiplier
    o = _causal_attention(q, k, v, scale, block, remat)
    return _mm(o.reshape(t, h * dh), wt("wo"), round_to)


def embed(params, ids, *, embedding_multiplier, omit=()):
    by = 1.0 if "embedding_multiplier" in omit else embedding_multiplier
    return jnp.asarray(params["embed"], F32)[ids] * by


def layer(x, lp, *, kind: str, residual_multiplier, rms_eps, ssm, attn,
          lora=None, block=512, omit=(), round_to=None, remat=False):
    """One layer on the stream ``x`` [T, D]; ``kind`` is ``"mamba"`` or
    ``"attention"`` (the configuration's ``layer_types``); ``ssm`` and
    ``attn`` hold the widths of :func:`mamba_mixer` and
    :func:`attention_mixer`; ``lp`` / ``lora`` are the layer's own
    entries."""
    f32 = lambda a: jnp.asarray(a, F32)
    ll = lora or {}
    t = x.shape[0]
    block = min(block, t)
    by = 1.0 if "residual_multiplier" in omit else residual_multiplier
    y = _rms(x, f32(lp["attn_norm"]), rms_eps)
    common = dict(omit=omit, round_to=round_to, remat=remat)
    if kind == "mamba":
        o = mamba_mixer(y, lp, ll, rms_eps=rms_eps, **ssm, **common)
    else:
        assert kind == "attention", kind
        assert t % block == 0, (t, block)
        o = attention_mixer(y, lp, ll, block=block, **attn, **common)
    x = x + by * o
    m = _rms(x, f32(lp["mlp_norm"]), rms_eps)
    mats = [_weight(lp[n], ll.get(n)) for n in ("w_gate", "w_up", "w_down")]
    ffn = lambda rows: _swiglu(rows, *mats, round_to)
    return x + by * _by_rows(ffn, m, block, remat and t % block == 0)


def logits(x, params, *, rms_eps, logits_scaling, last=None, omit=(),
           round_to=None):
    """``norm(x) E^T / logits_scaling`` of the last ``last`` positions
    (all when None)."""
    if last is not None:
        x = x[-last:]
    x = _rms(x, jnp.asarray(params["final_norm"], F32), rms_eps)
    by = 1.0 if "logits_scaling" in omit else logits_scaling
    return _mm(x, jnp.asarray(params["embed"], F32).T, round_to) / by


def head_loss(x, params, ids, *, block=512, **kw):
    """Mean next-token cross entropy of one sequence from the stream
    after the last layer, the logits a block of rows at a time (8,192 x
    100,352 float32 are 3.3 GB): memory, not mathematics."""
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
    return jnp.sum(nll[:-1]) / (t - 1)  # the last position has no target


def hidden(params, ids, *, layer_types, embedding_multiplier, lora=None,
           omit=(), round_to=None, remat=False, **widths):
    """The stream after the last layer of ``params`` for ONE sequence
    ``ids`` [T]; ``widths`` are :func:`layer`'s."""
    assert set(omit) <= set(PIECES), omit
    assert len(layer_types) == len(params["layers"])
    lora_layers = (lora or {}).get("layers", {})
    x = embed(params, ids, embedding_multiplier=embedding_multiplier,
              omit=omit)
    for i, (kind, lp) in enumerate(zip(layer_types, params["layers"])):
        def one(x, lp, ll, kind=kind):
            return layer(x, lp, kind=kind, lora=ll, omit=omit,
                         round_to=round_to, remat=remat, **widths)

        if remat:
            one = jax.checkpoint(one)
        x = one(x, lp, lora_layers.get(str(i), {}))
    return x


def forward(params, ids, *, logits_scaling, last=None, **kw):
    """Logits ``[last, vocab]`` (all positions when ``last`` is None)."""
    x = hidden(params, ids, **kw)
    return logits(
        x, params, rms_eps=kw["rms_eps"], logits_scaling=logits_scaling,
        last=last, omit=kw.get("omit", ()), round_to=kw.get("round_to"),
    )


def loss(params, ids, *, logits_scaling, **kw):
    """Mean next-token cross entropy of one sequence, float32."""
    x = hidden(params, ids, **kw)
    return head_loss(
        x, params, ids, rms_eps=kw["rms_eps"], logits_scaling=logits_scaling,
        omit=kw.get("omit", ()), round_to=kw.get("round_to"),
    )


def lora_gradients(params, lora, ids, **kw):
    """(loss, d loss / d every adapter leaf) by ``jax.grad``."""
    return jax.value_and_grad(
        lambda l: loss(params, ids, lora=l, **kw)
    )(lora)
