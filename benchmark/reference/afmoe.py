"""Plain reference of the AFMoE decoder (arcee-ai Trinity family;
``transformers`` ``models/afmoe/modeling_afmoe.py``) as one chip's share
of it runs here: forward, loss, and gradients by ``jax.grad``.

Straightforward ``jax.numpy`` in float32; callers wrap it in
``jax.default_matmul_precision("highest")``.  No kernel, no sort, no
remat: attention in query blocks under an explicit mask, every HELD
expert on every token, masked by the selection.  Imports nothing from
``rayfed_tpu``; takes the system's parameter tree (a list of layer
dicts, any float dtype) so both sides read the same weights.

With ``h`` the residual stream ``[T, D]``:

- ``h0 = E[ids] * embed_scale`` (``mup_enabled``: ``sqrt(D)``).
- ``a = rms(h; g_in)``; ``q, k, v, z = a Wq, a Wk, a Wv, a Wz``;
  ``q, k`` RMS-normed over the head width.
- sliding layer: rotary positions on ``q, k``, key ``j`` visible to
  query ``i`` iff ``i - window < j <= i``; full layer: no position
  embedding, ``j <= i``.  Scores ``q k^T / sqrt(head_dim)``, softmax.
- ``o = attention * sigmoid(z)``; ``h += rms(o Wo; g_post_attn)``.
- ``m = rms(h; g_pre_mlp)``.  Dense layer: SwiGLU.  Expert layer:
  ``s = sigmoid(m Wr)``, ``S = top_k(s + b)``, ``w_e = route_scale *
  s_e / (sum_{S} s + 1e-20)`` over ALL selected experts, ``f =
  shared(m) + sum_{e in S, e held} w_e expert_e(m)``.  What the experts
  on other chips would add is left out, as in the program.
- ``h += rms(f; g_post_mlp)``; logits ``rms(h; g_final) W_head``.

Departures from the published code, all of them layouts that random
weights make immaterial: rotary pairs are interleaved (the layout
``rayfed_tpu`` stores its weights for), and the output gate is a matrix
of its own (``wz``) beside ``wq``.

``omit`` removes one piece of the mathematics; the tests use it to show
that the comparison notices each: ``qk_norm``, ``output_gate``,
``rope_only_on_window`` (rotary positions on full layers too),
``post_norms``, ``route_scale``, ``bias``, ``norm_over_all`` (normalise
over the held experts only).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
PIECES = ("qk_norm", "output_gate", "rope_only_on_window", "post_norms",
          "route_scale", "bias", "norm_over_all")


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: [T, H, Dh]; rotates pairs (x[..., 0::2], x[..., 1::2])."""
    t, _, dh = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).reshape(x.shape)


def _masked_attention(q, k, v, window, block):
    """q, k, v: [T, H, Dh] (kv heads already repeated); ``window`` None
    for full causal attention."""
    t, h, dh = q.shape
    key_pos = jnp.arange(t)

    def one_block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block, 0)
        q_pos = i * block + jnp.arange(block)
        s = jnp.einsum("qhd,khd->hqk", qb, k) * dh ** -0.5
        seen = key_pos[None, :] <= q_pos[:, None]
        if window is not None:
            seen &= key_pos[None, :] > q_pos[:, None] - window
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(one_block, jnp.arange(t // block))
    return out.reshape(t, h, dh)


def _weight(w, entry):
    """The matrix with its adapter merged: ``W + A B * scale``."""
    w = jnp.asarray(w, F32)
    if entry is None:
        return w
    a, b = jnp.asarray(entry["a"], F32), jnp.asarray(entry["b"], F32)
    return w + jnp.einsum("...ir,...ro->...io", a, b) * entry["scale"]


def _mm(a, b, round_to=None):
    """``a @ b`` (``b`` may stack one matrix an expert: ``[E, in, out]``
    gives ``[E, T, out]``); with ``round_to`` both operands pass through
    that type first: the reference "one precision below", which the
    comparison that decides ``correct`` must refuse
    (``benchmark/tests/test_chip_afmoe.py``)."""
    if round_to is not None:
        a, b = (v.astype(round_to).astype(F32) for v in (a, b))
    return a @ b


def _swiglu(m, w_gate, w_up, w_down, round_to=None):
    h = jax.nn.silu(_mm(m, w_gate, round_to)) * _mm(m, w_up, round_to)
    return _mm(h, w_down, round_to)


def expert_layer(m, p, *, held, top_k, route_scale, lora=None,
                 selected=None, omit=(), round_to=None):
    """``m`` [T, D] -> (``f`` [T, D], ``info``).  ``held``: the expert
    ids whose weights ``p["experts"]`` stacks, in that order.  ``info``:
    ``biased`` [T, E] the scores the selection ranks (``s + b``),
    ``selected`` [T, k], ``counts`` [held] tokens per held expert."""
    lora = lora or {}
    s = jax.nn.sigmoid(_mm(m, jnp.asarray(p["router"], F32), round_to))
    biased = s if "bias" in omit else s + jnp.asarray(p["router_bias"], F32)
    if selected is None:
        _, selected = jax.lax.top_k(biased, top_k)
    chosen = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], selected
    ].set(True)
    among = chosen
    if "norm_over_all" in omit:
        among = chosen & jnp.zeros(s.shape[1], bool).at[jnp.asarray(held, jnp.int32)].set(True)
    scale = 1.0 if "route_scale" in omit else route_scale
    w = scale * s / (jnp.sum(jnp.where(among, s, 0.0), -1, keepdims=True) + 1e-20)
    w = jnp.where(chosen, w, 0.0)
    ls = lora.get("shared", {})
    f = _swiglu(m, *(
        _weight(p["shared"][n], ls.get(n))
        for n in ("w_gate", "w_up", "w_down")
    ), round_to)
    le = lora.get("experts", {})
    mats = [
        _weight(p["experts"][n], le.get(n)) for n in ("w_gate", "w_up", "w_down")
    ]
    if held:  # every held expert on every token: [held, T, D]
        each = _swiglu(m, *mats, round_to)
        f = f + jnp.einsum("te,etd->td", w[:, jnp.asarray(held)], each)
    info = {
        "biased": biased,
        "selected": selected,
        "counts": jnp.sum(chosen[:, jnp.asarray(held, jnp.int32)], axis=0),
    }
    return f, info


def forward(params, ids, *, layer_types, num_dense_layers, num_heads,
            num_kv_heads, head_dim, window, rope_theta, rms_eps,
            embed_scale, held, top_k, route_scale, lora=None,
            selected=None, last=None, block=512, omit=(), round_to=None):
    """Logits ``[last, vocab]`` (all positions when ``last`` is None) of
    ONE sequence ``ids`` [T] through ``len(layer_types)`` layers, and
    ``{layer index: info}`` of the expert layers.  ``selected`` maps
    layer indices to the experts to use in place of the top-k."""
    f32 = lambda a: jnp.asarray(a, F32)
    assert set(omit) <= set(PIECES), omit
    lora_layers = (lora or {}).get("layers", {})
    selected = selected or {}
    x = f32(params["embed"])[ids] * embed_scale
    t = x.shape[0]
    block = min(block, t)
    assert t % block == 0, (t, block)
    infos = {}
    for i, kind in enumerate(layer_types):
        lp, ll = params["layers"][i], lora_layers.get(str(i), {})
        wt = lambda name: _weight(lp[name], ll.get(name))
        mm = lambda a, b: _mm(a, b, round_to)
        a = _rms(x, f32(lp["attn_norm"]), rms_eps)
        q = mm(a, wt("wq")).reshape(t, num_heads, head_dim)
        k = mm(a, wt("wk")).reshape(t, num_kv_heads, head_dim)
        v = mm(a, wt("wv")).reshape(t, num_kv_heads, head_dim)
        if "qk_norm" not in omit:
            q = _rms(q, f32(lp["q_norm"]), rms_eps)
            k = _rms(k, f32(lp["k_norm"]), rms_eps)
        sliding = kind == "sliding_attention"
        if sliding or "rope_only_on_window" in omit:
            q, k = _rope(q, rope_theta), _rope(k, rope_theta)
        reps = num_heads // num_kv_heads
        k, v = jnp.repeat(k, reps, axis=1), jnp.repeat(v, reps, axis=1)
        o = _masked_attention(q, k, v, window if sliding else None, block)
        o = o.reshape(t, num_heads * head_dim)
        if "output_gate" not in omit:
            o = o * jax.nn.sigmoid(mm(a, wt("wz")))
        o = mm(o, wt("wo"))
        if "post_norms" not in omit:
            o = _rms(o, f32(lp["post_attn_norm"]), rms_eps)
        x = x + o
        m = _rms(x, f32(lp["mlp_norm"]), rms_eps)
        if i < num_dense_layers:
            f = _swiglu(m, wt("w_gate"), wt("w_up"), wt("w_down"), round_to)
        else:
            f, infos[i] = expert_layer(
                m, lp["moe"], held=held, top_k=top_k,
                route_scale=route_scale, lora=ll.get("moe"),
                selected=selected.get(i), omit=omit, round_to=round_to,
            )
        if "post_norms" not in omit:
            f = _rms(f, f32(lp["post_mlp_norm"]), rms_eps)
        x = x + f
    if last is not None:
        x = x[-last:]
    x = _rms(x, f32(params["final_norm"]), rms_eps)
    return _mm(x, f32(params["lm_head"]), round_to), infos


def next_token_loss(logits, ids):
    """Mean cross entropy of ``logits`` [T, V] at the next token."""
    logp = jax.nn.log_softmax(logits[:-1])
    return -jnp.mean(jnp.take_along_axis(logp, ids[1:, None], axis=-1))


def loss(params, ids, **kw):
    """Mean next-token cross entropy of one sequence, float32."""
    return next_token_loss(forward(params, ids, **kw)[0], ids)


def lora_gradients(params, lora, ids, **kw):
    """(loss, d loss / d every adapter leaf) by ``jax.grad``."""
    return jax.value_and_grad(
        lambda l: loss(params, ids, lora=l, **kw)
    )(lora)


def routing_agreement(biased, system_selected, top_k):
    """Part (a) of the comparison across the selection's discontinuity:
    for every expert the system selected, how far its reference score
    ``s + b`` lies BELOW the reference's ``top_k``-th best (0 where it
    is among them); and the share of (token, choice) pairs both sides
    selected.  Returns ``(worst shortfall, exact share)``."""
    kth = jax.lax.top_k(biased, top_k)[0][:, -1:]
    picked = jnp.take_along_axis(biased, system_selected, axis=-1)
    shortfall = jnp.maximum(kth - picked, 0.0)
    return jnp.max(shortfall), jnp.mean(picked >= kth)
