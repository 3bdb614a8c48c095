"""Plain reference of the ``nemotron_h`` decoder (NVIDIA Nemotron-3
Super; ``transformers`` ``models/nemotron_h/modeling_nemotron_h.py``:
``NemotronHBlock``, ``NemotronHMamba2Mixer``, ``MambaRMSNormGated``,
``NemotronHAttention``, ``NemotronHMOE``; NVIDIA's LatentMoE; the
multi-token-prediction module of DeepSeek-V3 section 2.2 as Megatron-Core
runs it) as one chip's share of it runs here: forward, both losses, and
gradients by ``jax.grad``.

Straightforward ``jax.numpy`` in float32; callers wrap it in
``jax.default_matmul_precision("highest")``.  No kernel, no sort, no
chunked form: BLOCK BY BLOCK as published (each block one part, not the
program's pairs), the state-space recurrence TOKEN BY TOKEN, the
attention in query blocks under an explicit mask, every HELD expert on
every token, weighted by the selection.  Imports nothing from
``rayfed_tpu``.  It reads the system's parameter tree (:func:`blocks`
takes the blocks out of its layers by the published pattern) so that
both sides read the same weights, and upcasts a block's weights where it
uses them (``embed`` / ``block`` / ``logits`` / ``head_loss`` can be
called one at a time, so that one block's float32 copy lives at once).

With ``x`` the residual stream ``[T, D]``, RMS norms with ``eps``:

- ``x_0 = E[ids]``; every block ``x <- x + part(norm(x))``; after the
  last ``norm_f``, ``logits = x W_head`` (untied) over the vocabulary
  slice; mean next-token cross entropy.
- **M** (Mamba-2): ``H`` heads of width ``P``, state ``N``, ``G`` groups,
  ``d_inner = H P``.  ``[z | xBC | dt] = y W_in``; ``xBC`` through the
  causal depthwise convolution with bias and ``silu``; ``[x | B | C]``
  (``B``, ``C`` one ``N``-vector a group, a group serving ``H / G``
  heads); ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``; ``h_t =
  exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t``, ``y_t = h_t C_t + D x_t``;
  ``u = y * silu(z)``, normed over EACH of ``G`` groups of ``d_inner /
  G`` channels (``MambaRMSNormGated(group_size = d_inner / n_groups)``),
  times the norm's weight; ``o W_out``.
- **E** (latent mixture of experts): ``s = sigmoid(y W_r)``; the top
  ``k`` of ``s + b`` (``b`` steers the selection only); weights the
  selected scores over their sum, times ``routed_scaling_factor``, over
  ALL selected experts whether held here or not; ``y_l = y W_lat_in``;
  ``part = (sum_{e selected, held} w_e relu(y_l U_e)^2 D_e) W_lat_out +
  relu(y U_s)^2 D_s``.  What the experts on other chips would add is
  left out, as in the program.
- **\\*** (attention): ``H`` query heads, ``KV`` key/value heads, no
  position embedding, scores ``q . k / sqrt(head_dim)``, causal softmax,
  ``o W_o``; no bias.
- **MTP**: ``m_i = [norm_e(E[ids_{i+1}]) ; norm_h(h_i)] W_eh`` with
  ``h`` the stream after the last block before ``norm_f``; the module's
  blocks as above; its own final norm; the shared head predicts
  ``ids_{i+2}``; the last two positions have no target.  The loss is
  ``main + mtp_loss_weight * mtp``.

Departures from the published code: the E block's weights are applied
in the order that the latent projection's linearity allows (the
weighting before ``W_lat_out``, where the published code sums after
it); the position that the MTP module's input lacks a next token for
(the last) reads the first token's embedding, and it has no target.
The published MTP module is shared between depths; one depth is run.

``omit`` removes or breaks one piece of the mathematics; the tests use
it to show that the comparison notices each: ``norm_groups`` (the gated
norm over all of ``d_inner``), ``relu2`` (``relu`` without the square),
``latent`` (the routed sum not projected back up: the latent pair
dropped, the experts read the first ``latent`` channels), ``shared``
(the shared expert left out), ``route_scale``, ``bias``,
``mtp_shift`` (the MTP module's targets one token too early),
``mtp_embedding`` (its input reads token ``i``'s embedding, not
``i + 1``'s).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.afmoe import (  # noqa: F401  (re-exported)
    F32,
    _mm,
    _rms,
    _weight,
    routing_agreement,
)
from benchmark.reference.granite_hybrid import recurrence
from benchmark.reference.kimi_k2 import _by_rows, _causal_attention

PIECES = ("norm_groups", "relu2", "latent", "shared", "route_scale", "bias",
          "mtp_shift", "mtp_embedding")


def blocks(layers, pattern: str, lora_layers=None):
    """``[(kind, weights, adapters), ...]``: the published blocks of
    ``pattern`` (``M``, ``E``, ``*``) out of the system's layers, each a
    mixer part (``attn_norm`` and its mixer's matrices) and an FFN part
    (``mlp_norm`` and ``moe``), or a mixer alone where two mixer blocks
    follow each other.  ``layers``: the per-layer list of
    ``decoder.unstack``; ``lora_layers`` its adapters by index."""
    lora_layers = lora_layers or {}
    out, i = [], -1
    for j, kind in enumerate(pattern):
        if kind == "E" and j and pattern[j - 1] != "E":
            lp, ll = layers[i], lora_layers.get(str(i), {})
            out.append(("E", {"norm": lp["mlp_norm"], **lp["moe"]},
                        ll.get("moe", {})))
            continue
        assert kind in "M*", pattern
        i += 1
        lp, ll = layers[i], lora_layers.get(str(i), {})
        mixer = {k: v for k, v in lp.items()
                 if k not in ("attn_norm", "mlp_norm", "moe")}
        out.append((kind, {"norm": lp["attn_norm"], **mixer},
                    {k: v for k, v in ll.items() if k != "moe"}))
    assert i == len(layers) - 1, (i, len(layers))
    return out


def _relu2(m, up, down, round_to, omit=()):
    h = jax.nn.relu(_mm(m, up, round_to))
    return _mm(h if "relu2" in omit else h * h, down, round_to)


def mamba(y, p, lora, *, heads, head_dim, state, groups, conv_width,
          rms_eps, omit=(), round_to=None, remat=False):
    """The Mamba-2 part on the normed stream ``y`` [T, D]."""
    f32 = lambda v: jnp.asarray(v, F32)
    t = y.shape[0]
    d_inner, gn, k = heads * head_dim, groups * state, conv_width
    proj = _mm(y, _weight(p["w_in"], lora.get("w_in")), round_to)
    z = proj[:, :d_inner]
    xbc = proj[:, d_inner: 2 * d_inner + 2 * gn]
    dt = jax.nn.softplus(proj[:, 2 * d_inner + 2 * gn:] + f32(p["dt_bias"]))
    padded = jnp.pad(xbc, [(k - 1, 0), (0, 0)])
    w = f32(p["conv_w"])
    xbc = jax.nn.silu(
        sum(padded[j: j + t] * w[:, j] for j in range(k)) + f32(p["conv_b"])
    )
    x = xbc[:, :d_inner].reshape(t, heads, head_dim)
    rep = lambda v: jnp.repeat(
        v.reshape(t, groups, state), heads // groups, axis=1
    )
    b, c = rep(xbc[:, d_inner: d_inner + gn]), rep(xbc[:, d_inner + gn:])
    if round_to is not None:  # the operands of the scan's products
        x, b, c = (v.astype(round_to).astype(F32) for v in (x, b, c))
    s = recurrence(
        x, dt, -jnp.exp(f32(p["A_log"])), b, c, f32(p["D"]), remat=remat
    ).reshape(t, d_inner)
    u = s * jax.nn.silu(z)
    parts = 1 if "norm_groups" in omit else groups
    u = _rms(u.reshape(t, parts, d_inner // parts), 1.0, rms_eps)
    u = u.reshape(t, d_inner) * f32(p["ssm_norm"])
    return _mm(u, _weight(p["w_out"], lora.get("w_out")), round_to)


def experts(y, p, lora, *, held, top_k, route_scale, selected=None,
            omit=(), round_to=None, remat=False):
    """The latent expert part on the normed stream ``y`` [T, D] ->
    (``part``, ``info``): ``biased`` [T, E] the scores the selection
    ranks, ``selected`` [T, k], ``counts`` [held]."""
    names = ("w_up", "w_down")
    s = jax.nn.sigmoid(_mm(y, jnp.asarray(p["router"], F32), round_to))
    biased = s if "bias" in omit else s + jnp.asarray(p["router_bias"], F32)
    if selected is None:
        _, selected = jax.lax.top_k(biased, top_k)
    chosen = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], selected
    ].set(True)
    scale = 1.0 if "route_scale" in omit else route_scale
    w = scale * s / (jnp.sum(jnp.where(chosen, s, 0.0), -1, keepdims=True)
                     + 1e-20)
    w = jnp.where(chosen, w, 0.0)
    if "latent" in omit:
        y_l = y[:, : p["w_lat_in"].shape[1]]
    else:
        y_l = _mm(y, _weight(p["w_lat_in"], lora.get("w_lat_in")), round_to)
    le = lora.get("experts", {})

    def one_expert(y_l, weight, mats, a, b):
        # one held expert on every token, weighted by the selection
        ws = [_weight(mats[n], None if n not in le else
                      dict(a=a[n], b=b[n], scale=le[n]["scale"]))
              for n in names]
        return weight[:, None] * _relu2(y_l, *ws, round_to, omit)

    if remat:  # an expert's float32 copy is made again in the backward pass
        one_expert = jax.checkpoint(one_expert)

    def add_expert(routed, one):
        # the held experts one after another (a loop, so that a program
        # holds one expert's body however many are held)
        weight, mats, a, b = one
        return routed + one_expert(y_l, weight, mats, a, b), None

    routed, _ = jax.lax.scan(add_expert, jnp.zeros_like(y_l), (
        w[:, jnp.asarray(held, jnp.int32)].T,
        {n: p["experts"][n] for n in names},
        {n: le[n]["a"] for n in names if n in le},
        {n: le[n]["b"] for n in names if n in le},
    ))
    if "latent" in omit:
        part = jnp.pad(routed, [(0, 0), (0, y.shape[1] - routed.shape[1])])
    else:
        part = _mm(routed, _weight(p["w_lat_out"], lora.get("w_lat_out")),
                   round_to)
    if "shared" not in omit:
        ls = lora.get("shared", {})
        part = part + _by_rows(
            lambda rows: _relu2(
                rows, *(_weight(p["shared"][n], ls.get(n)) for n in names),
                round_to, omit,
            ), y, min(512, y.shape[0]), remat and y.shape[0] % 512 == 0,
        )
    info = {
        "biased": biased,
        "selected": selected,
        "counts": jnp.sum(chosen[:, jnp.asarray(held, jnp.int32)], axis=0),
    }
    return part, info


def attention(y, p, lora, *, num_heads, num_kv_heads, attn_head_dim, block,
              round_to=None, remat=False):
    """Causal attention without positions on the normed stream ``y``."""
    t, h, kv, dh = y.shape[0], num_heads, num_kv_heads, attn_head_dim
    wt = lambda name: _weight(p[name], lora.get(name))
    q = _mm(y, wt("wq"), round_to).reshape(t, h, dh)
    k = _mm(y, wt("wk"), round_to).reshape(t, kv, dh)
    v = _mm(y, wt("wv"), round_to).reshape(t, kv, dh)
    k, v = (jnp.repeat(a, h // kv, axis=1) for a in (k, v))
    o = _causal_attention(q, k, v, dh ** -0.5, block, remat)
    return _mm(o.reshape(t, h * dh), wt("wo"), round_to)


def block(x, p, lora=None, *, kind: str, rms_eps, ssm, attn, moe,
          selected=None, omit=(), round_to=None, remat=False):
    """One published block ``x + part(norm(x))`` on the stream ``x``
    [T, D] -> (``x``, ``info`` of an E block or None).  ``ssm``,
    ``attn`` and ``moe`` hold the widths of :func:`mamba`,
    :func:`attention` and :func:`experts`."""
    lora = lora or {}
    y = _rms(x, jnp.asarray(p["norm"], F32), rms_eps)
    common = dict(omit=omit, round_to=round_to, remat=remat)
    if kind == "M":
        return x + mamba(y, p, lora, rms_eps=rms_eps, **ssm, **common), None
    if kind == "*":
        t = x.shape[0]
        blk = min(512, t)
        assert t % blk == 0, (t, blk)
        return x + attention(y, p, lora, block=blk, round_to=round_to,
                             remat=remat, **attn), None
    assert kind == "E", kind
    part, info = experts(y, p, lora, selected=selected, **moe, **common)
    return x + part, info


def embed(params, ids):
    return jnp.asarray(params["embed"], F32)[ids]


def mtp_input(x0_next, h, p, lora=None, *, rms_eps, round_to=None):
    """``[norm_e(x0_next) ; norm_h(h)] W_eh``: ``x0_next`` [T, D] the
    embeddings of the tokens one ahead, ``h`` the stream after the last
    block before ``norm_f``; ``p`` the module's weights."""
    e = _rms(x0_next, jnp.asarray(p["enorm"], F32), rms_eps)
    hh = _rms(h, jnp.asarray(p["hnorm"], F32), rms_eps)
    w = _weight(p["w_eh"], (lora or {}).get("w_eh"))
    return _mm(jnp.concatenate([e, hh], -1), w, round_to)


def logits(x, final_norm, head, *, rms_eps, last=None, round_to=None):
    """``norm(x) W_head`` of the last ``last`` positions (all when
    None)."""
    if last is not None:
        x = x[-last:]
    x = _rms(x, jnp.asarray(final_norm, F32), rms_eps)
    return _mm(x, jnp.asarray(head, F32), round_to)


def head_loss(x, final_norm, head, ids, *, shift=1, block=512, **kw):
    """Mean cross entropy of one sequence at the token ``shift`` ahead,
    from the stream after the last block, the logits a block of rows at a
    time: memory, not mathematics.  The last ``shift`` positions have no
    target."""
    t = x.shape[0]
    block = min(block, t)
    if t % block:
        block = t
    targets = jnp.roll(ids, -shift)

    def rows(args):
        xb, tb = args
        logp = jax.nn.log_softmax(logits(xb, final_norm, head, **kw), -1)
        return -jnp.take_along_axis(logp, tb[:, None], axis=-1)[:, 0]

    nll = jax.lax.map(
        jax.checkpoint(rows),
        (x.reshape(t // block, block, -1), targets.reshape(t // block, block)),
    ).reshape(t)
    return jnp.sum(nll[: t - shift]) / (t - shift)


def run(params, ids, *, pattern, mtp_pattern, rms_eps, ssm, attn, moe,
        mtp_loss_weight, lora=None, selected=None, last=None, omit=(),
        round_to=None, remat=False):
    """ONE sequence ``ids`` [T] through the blocks of ``pattern``, then
    the MTP module's of ``mtp_pattern``: ``(total loss, (main, mtp),
    main logits of the last ``last`` positions (None: none made),
    [info of every E block in order, the module's last])``.
    ``params``: ``decoder.unstack`` of the system's; ``selected``: one
    selection an E block, in that order, in place of the top-k."""
    assert set(omit) <= set(PIECES), omit
    lora = lora or {}
    picks = list(selected or [])
    infos = []
    widths = dict(rms_eps=rms_eps, ssm=ssm, attn=attn, moe=moe, omit=omit,
                  round_to=round_to, remat=remat)

    def through(x, chain):
        for kind, p, ll in chain:
            def one(x, p, ll, chosen, kind=kind):
                return block(x, p, ll, kind=kind, selected=chosen, **widths)

            if remat:
                one = jax.checkpoint(one)
            chosen = picks[len(infos)] if picks and kind == "E" else None
            x, info = one(x, p, ll, chosen)
            if info is not None:
                infos.append(info)
        return x

    x0 = embed(params, ids)
    h = through(x0, blocks(params["layers"], pattern, lora.get("layers")))
    head, fin = params["lm_head"], params["final_norm"]
    main = head_loss(h, fin, head, ids, rms_eps=rms_eps, round_to=round_to)
    out = None if last is None else logits(
        h, fin, head, rms_eps=rms_eps, last=last, round_to=round_to
    )
    p, lm = params["mtp"], lora.get("mtp", {})
    ahead = ids if "mtp_embedding" in omit else jnp.roll(ids, -1)
    m = mtp_input(embed(params, ahead), h, p, lm, rms_eps=rms_eps,
                  round_to=round_to)
    m = through(m, blocks(p["layers"], mtp_pattern, lm.get("layers")))
    mtp = head_loss(m, p["final_norm"], head, ids, rms_eps=rms_eps,
                    round_to=round_to,
                    shift=1 if "mtp_shift" in omit else 2)
    return main + mtp_loss_weight * mtp, (main, mtp), out, infos


def lora_gradients(params, lora, ids, **kw):
    """(total loss, d loss / d every adapter leaf) by ``jax.grad``."""
    return jax.value_and_grad(lambda l: run(params, ids, lora=l, **kw)[0])(
        lora
    )
