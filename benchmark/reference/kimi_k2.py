"""Plain reference of the ``kimi_k2`` decoder (moonshotai Kimi-K2; the
DeepSeek-V3 block: ``transformers`` ``models/deepseek_v3/
modeling_deepseek_v3.py``: ``DeepseekV3Attention``,
``DeepseekV3TopkRouter``, ``DeepseekV3MoE``; frequencies by
``modeling_rope_utils.py::_compute_yarn_parameters``) as one chip's
share of it runs here: forward, loss, and gradients by ``jax.grad``.

Straightforward ``jax.numpy`` in float32; callers wrap it in
``jax.default_matmul_precision("highest")``.  No kernel, no sort: the
attention in query blocks under an explicit mask on the CONCATENATED
query-key width, the held experts one after another on every token,
weighted by the selection.  Imports nothing from ``rayfed_tpu``; takes
the system's parameter tree (a list of layer dicts, any float dtype) so
both sides read the same weights, and upcasts a layer's weights where
it uses them (``embed`` / ``layer`` / ``logits`` can be called one at a
time, so that one layer's float32 copy lives at once).

With ``x`` the residual stream ``[T, D]``, RMS norms with ``eps``, ``H``
heads, per head the widths ``nope | rope`` of queries and keys and
``v_dim`` of values:

- ``y = norm(x)``; ``c_q = norm(y W_qa)``; ``[q_nope | q_pe] = c_q W_qb``
  a head.
- ``[c_kv | k_pe] = y W_kva`` (``k_pe`` ONE head, shared by all);
  ``c_kv = norm(c_kv)``; ``[k_nope | v] = c_kv W_kvb`` a head.
- ``q_pe, k_pe <- rope(.)`` on the ``rope`` dims.  YaRN: ``f_i =
  theta^(-2i/rope)``, ``g_i = f_i / factor``; ``d(n) = rope ln(L / (2 pi
  n)) / (2 ln theta)`` with ``L`` the original positions; ``low =
  floor(d(beta_fast))``, ``high = ceil(d(beta_slow))`` clipped to ``[0,
  rope - 1]``; ``ramp_i = clip((i - low) / (high - low), 0, 1)``;
  ``inv_freq_i = g_i ramp_i + f_i (1 - ramp_i)``; cos and sin times
  ``m(mscale) / m(mscale_all_dim)``, ``m(s) = 0.1 s ln(factor) + 1``.
- ``s = (q_nope . k_nope + q_pe . k_pe) (nope + rope)^-0.5
  m(mscale_all_dim)^2``; causal softmax; ``o = P v``; ``x = x +
  concat(o) W_o``.  No head norm, no gate, no norm after the sub-block.
- ``y = norm(x)``.  Dense layer: ``x + SwiGLU(y)``.  Expert layer:
  ``scores = sigmoid(y W_r)``; chosen = top-k of ``scores + b``
  (``e_score_correction_bias``: selection only); ``w = scores[chosen] /
  (sum + 1e-20) x routed_scaling_factor`` over ALL chosen experts
  whether held here or not; ``x + sum_{e chosen, held} w_e
  SwiGLU^(e)(y) + SwiGLU^shared(y)``.  What the experts on other chips
  would add is left out, as in the program.
- logits ``norm(x) W_head`` over the vocabulary slice; mean next-token
  cross entropy.

Departures from the published code, all of them layouts that random
weights make immaterial: rotary pairs are interleaved (the layout
``rayfed_tpu`` stores its weights for; the published code de-interleaves
a checkpoint's rows first), and ``n_group = topk_group = 1`` makes the
group-limited selection the plain top-k.

``omit`` removes one piece of the mathematics; the tests use it to show
that the comparison notices each: ``latent_norms``, ``yarn`` (plain
frequencies), ``softmax_mscale``, ``shared_rope_key`` (zero rotary
score), ``route_scale``, ``bias``, ``norm_over_all``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.afmoe import (  # noqa: F401  (re-exported)
    F32,
    _mm,
    _rms,
    _swiglu,
    _weight,
    next_token_loss,
    routing_agreement,
)

PIECES = ("latent_norms", "yarn", "softmax_mscale", "shared_rope_key",
          "route_scale", "bias", "norm_over_all")


def yarn_magnitude(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_blend(rope_dim: int, theta: float, yarn: dict):
    """``(low, high)`` of the ramp."""
    def d(n):
        return rope_dim * math.log(
            yarn["original_max_position_embeddings"] / (n * 2 * math.pi)
        ) / (2 * math.log(theta))

    return (max(math.floor(d(yarn["beta_fast"])), 0),
            min(math.ceil(d(yarn["beta_slow"])), rope_dim - 1))


def inv_freq(rope_dim: int, theta: float, yarn=None):
    """The ``rope_dim / 2`` frequencies; ``yarn`` is the config's
    ``rope_scaling`` group, None for plain ones."""
    f = 1.0 / theta ** (jnp.arange(0, rope_dim, 2, dtype=F32) / rope_dim)
    if yarn is None:
        return f
    low, high = yarn_blend(rope_dim, theta, yarn)
    ramp = jnp.clip(
        (jnp.arange(rope_dim // 2, dtype=F32) - low)
        / (high - low if high != low else 0.001), 0.0, 1.0,
    )
    return f / yarn["factor"] * ramp + f * (1.0 - ramp)


def _rope(x, freqs, by):
    """x: [T, H, rope]; rotates pairs (x[..., 0::2], x[..., 1::2])."""
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :] * by, jnp.sin(ang)[:, None, :] * by
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).reshape(x.shape)


def _by_rows(f, x, block, remat):
    """``f(x)`` for a ``f`` that treats the rows of ``x`` [T, D] alike;
    with ``remat`` in row blocks, each made again in the backward pass
    (an 18,432-wide float32 hidden layer of 8,192 tokens is 0.6 GB a
    copy): memory, not mathematics."""
    if not remat:
        return f(x)
    out = jax.lax.map(jax.checkpoint(f), x.reshape(-1, block, x.shape[-1]))
    return out.reshape(x.shape[0], -1)


def _causal_attention(q, k, v, scale, block, remat):
    """q, k: [T, H, nope + rope]; v: [T, H, v_dim]; in query blocks."""
    t, h, _ = q.shape
    key_pos = jnp.arange(t)

    def one_block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block, 0)
        q_pos = i * block + jnp.arange(block)
        s = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        seen = key_pos[None, :] <= q_pos[:, None]
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    if remat:
        one_block = jax.checkpoint(one_block)
    out = jax.lax.map(one_block, jnp.arange(t // block))
    return out.reshape(t, h, v.shape[-1])


def expert_layer(m, p, *, held, top_k, route_scale, lora=None,
                 selected=None, omit=(), round_to=None, remat=False):
    """``m`` [T, D] -> (``f`` [T, D], ``info``).  ``held``: the expert
    ids whose weights ``p["experts"]`` stacks, in that order.  ``info``:
    ``biased`` [T, E] the scores the selection ranks (``s + b``),
    ``selected`` [T, k], ``counts`` [held] tokens per held expert."""
    lora = lora or {}
    names = ("w_gate", "w_up", "w_down")
    s = jax.nn.sigmoid(_mm(m, jnp.asarray(p["router"], F32), round_to))
    biased = s if "bias" in omit else s + jnp.asarray(p["router_bias"], F32)
    if selected is None:
        _, selected = jax.lax.top_k(biased, top_k)
    chosen = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], selected
    ].set(True)
    held_ids = jnp.asarray(held, jnp.int32)
    among = chosen
    if "norm_over_all" in omit:
        among = chosen & jnp.zeros(s.shape[1], bool).at[held_ids].set(True)
    scale = 1.0 if "route_scale" in omit else route_scale
    w = scale * s / (jnp.sum(jnp.where(among, s, 0.0), -1, keepdims=True) + 1e-20)
    w = jnp.where(chosen, w, 0.0)
    ls = lora.get("shared", {})
    f = _swiglu(m, *(_weight(p["shared"][n], ls.get(n)) for n in names),
                round_to)
    le = lora.get("experts", {})

    def one_expert(m, weight, w, a, b):
        # one held expert on every token, weighted by the selection
        mats = [
            _weight(w[n], None if n not in le else
                    dict(a=a[n], b=b[n], scale=le[n]["scale"]))
            for n in names
        ]
        return weight[:, None] * _swiglu(m, *mats, round_to)

    if remat:  # an expert's float32 copy is made again in the backward pass
        one_expert = jax.checkpoint(one_expert)
    for j, e in enumerate(held):
        f = f + one_expert(
            m, w[:, e],
            {n: p["experts"][n][j] for n in names},
            {n: le[n]["a"][j] for n in names if n in le},
            {n: le[n]["b"][j] for n in names if n in le},
        )
    info = {
        "biased": biased,
        "selected": selected,
        "counts": jnp.sum(chosen[:, held_ids], axis=0),
    }
    return f, info


def embed(params, ids):
    return jnp.asarray(params["embed"], F32)[ids]


def layer(x, lp, *, dense: bool, num_heads, kv_rank, nope_dim, rope_dim,
          v_dim, rope_theta, yarn, rms_eps, held, top_k, route_scale,
          lora=None, selected=None, block=512, omit=(), round_to=None,
          remat=False):
    """One layer on the stream ``x`` [T, D] -> (``x``, ``info``);
    ``lp`` / ``lora`` are the layer's own entries; ``info`` is None for
    a dense layer."""
    f32 = lambda a: jnp.asarray(a, F32)
    ll = lora or {}
    wt = lambda name: _weight(lp[name], ll.get(name))
    mm = lambda a, b: _mm(a, b, round_to)
    t, h = x.shape[0], num_heads
    block = min(block, t)
    assert t % block == 0, (t, block)
    norms = "latent_norms" not in omit
    y = _rms(x, f32(lp["attn_norm"]), rms_eps)
    c_q = mm(y, wt("wq_a"))
    if norms:
        c_q = _rms(c_q, f32(lp["q_a_norm"]), rms_eps)
    q = mm(c_q, wt("wq_b")).reshape(t, h, nope_dim + rope_dim)
    kv_a = mm(y, wt("wkv_a"))
    c_kv, k_pe = kv_a[:, :kv_rank], kv_a[:, kv_rank:]
    if norms:
        c_kv = _rms(c_kv, f32(lp["kv_a_norm"]), rms_eps)
    kv = mm(c_kv, wt("wkv_b")).reshape(t, h, nope_dim + v_dim)
    scaled = None if "yarn" in omit else yarn
    freqs = inv_freq(rope_dim, rope_theta, scaled)
    by, scale = 1.0, (nope_dim + rope_dim) ** -0.5
    if scaled:
        by = yarn_magnitude(yarn["factor"], yarn["mscale"]) / yarn_magnitude(
            yarn["factor"], yarn["mscale_all_dim"]
        )
    if yarn and yarn["mscale_all_dim"] and "softmax_mscale" not in omit:
        scale *= yarn_magnitude(yarn["factor"], yarn["mscale_all_dim"]) ** 2
    q_pe = _rope(q[..., nope_dim:], freqs, by)
    k_pe = _rope(k_pe[:, None, :], freqs, by)  # the one head all share
    if "shared_rope_key" in omit:
        k_pe = jnp.zeros_like(k_pe)
    q = jnp.concatenate([q[..., :nope_dim], q_pe], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope_dim], jnp.broadcast_to(k_pe, (t, h, rope_dim))], axis=-1
    )
    o = _causal_attention(q, k, kv[..., nope_dim:], scale, block, remat)
    x = x + mm(o.reshape(t, h * v_dim), wt("wo"))
    m = _rms(x, f32(lp["mlp_norm"]), rms_eps)
    if dense:
        mats = wt("w_gate"), wt("w_up"), wt("w_down")
        return x + _by_rows(
            lambda rows: _swiglu(rows, *mats, round_to), m, block, remat
        ), None
    f, info = expert_layer(
        m, lp["moe"], held=held, top_k=top_k, route_scale=route_scale,
        lora=ll.get("moe"), selected=selected, omit=omit, round_to=round_to,
        remat=remat,
    )
    return x + f, info


def logits(x, params, *, rms_eps, last=None, round_to=None):
    if last is not None:
        x = x[-last:]
    x = _rms(x, jnp.asarray(params["final_norm"], F32), rms_eps)
    return _mm(x, jnp.asarray(params["lm_head"], F32), round_to)


def forward(params, ids, *, num_dense_layers, lora=None, selected=None,
            last=None, omit=(), round_to=None, remat=False, **widths):
    """Logits ``[last, vocab]`` (all positions when ``last`` is None) of
    ONE sequence ``ids`` [T] through every layer of ``params``, and
    ``{layer index: info}`` of the expert layers.  ``selected`` maps
    layer indices to the experts to use in place of the top-k;
    ``widths`` are :func:`layer`'s; ``remat`` recomputes a layer (and an
    attention block) in the backward pass: memory, not mathematics."""
    assert set(omit) <= set(PIECES), omit
    lora_layers = (lora or {}).get("layers", {})
    selected = selected or {}
    x = embed(params, ids)
    infos = {}
    for i, lp in enumerate(params["layers"]):
        def one(x, lp, ll, chosen, i=i):
            return layer(
                x, lp, dense=i < num_dense_layers, lora=ll, selected=chosen,
                omit=omit, round_to=round_to, remat=remat, **widths,
            )

        if remat:
            one = jax.checkpoint(one)
        x, info = one(x, lp, lora_layers.get(str(i), {}), selected.get(i))
        if info is not None:
            infos[i] = info
    return logits(x, params, rms_eps=widths["rms_eps"], last=last,
                  round_to=round_to), infos


def loss(params, ids, **kw):
    """Mean next-token cross entropy of one sequence, float32."""
    return next_token_loss(forward(params, ids, **kw)[0], ids)


def lora_gradients(params, lora, ids, **kw):
    """(loss, d loss / d every adapter leaf) by ``jax.grad``."""
    return jax.value_and_grad(
        lambda l: loss(params, ids, lora=l, **kw)
    )(lora)
