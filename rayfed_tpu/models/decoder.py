"""A decoder whose block is described layer by layer.

:mod:`rayfed_tpu.models.llama` is one stacked scan of identical layers
(one attention kind, one FFN, model-wide).  Here every layer has a
:class:`LayerSpec`: its MIXER (a sliding window with rotary positions,
full causal attention with no position embedding, LATENT attention:
queries and keys/values through low-rank latents with a norm of their
own, a score of two parts, ``q_nope · k_nope`` a head plus ``q_pe ·
k_pe`` against ONE rotary key head all query heads share, and values of
their own width; or no attention at all but a state-space layer, the
Mamba-2 mixer of :mod:`rayfed_tpu.models.mamba2`: one input projection
split three ways, a causal depthwise convolution, the chunked scan of
:mod:`rayfed_tpu.ops.ssd`, a gated norm, an output projection; or
BLOCK-SPARSE attention, InfLLM-v2's: no positions, and beyond
``SparseConfig.dense_len`` tokens each query attends only to the key
blocks its K/V group selected, by scores against mean-pooled compressed
keys (:mod:`rayfed_tpu.ops.sparse_attention`: the selection in XLA, a
Pallas kernel pair whose key blocks are data, the choice kept by the
checkpoint); or DECAYED LINEAR attention, Lightning's: ``q``, ``k``,
``v`` a head each, rotated, a state a head that decays by a constant
rate a token (:class:`LightningConfig`), computed by the chunked scan of
:mod:`rayfed_tpu.ops.ssd` with one group a head, an output norm and a
gate) and its FFN kind (dense SwiGLU, routed + shared experts through
:func:`rayfed_tpu.models.moe.apply_expert_share`, or none: a block that
is its mixer alone, the ``nemotron_h`` block's single-part layers paired
so that a mixer block and the FFN block after it are one layer, each
part with its own pre-norm and plain residual, the arithmetic the same).
A multi-token-prediction module (:class:`MtpConfig`: DeepSeek-V3's and
``nemotron_h``'s MTP) may follow the layers: the embedding of the next
token and the last layer's output, each normed, concatenated and
projected (``w_eh``), layers of its own, a final norm of its own and the
shared head, which predicts the token after next; the LoRA step's loss
is then the main loss plus :data:`MTP_LOSS_WEIGHT` times that one.
What the sparse-expert decoders published since 2025 add to the block
is the configuration's to switch (:class:`DecoderConfig`, on by default
as the first configuration has them all): an RMS norm of q and k over
the head width, a sigmoid gate on the attention output, norms after
each sub-block, a scaled embedding; rotary frequencies may be YaRN's.
Off by default, the multipliers of the ``granitemoehybrid`` block: one
on each residual branch, a score scale that is not ``head_dim ** -0.5``,
one on the logits, and a head tied to the embedding (MiniCPM's muP
multipliers are three of them).

Consecutive layers with one FFN kind and one kind of mixer PARAMETERS
(window and full attention share theirs; latent attention, a
state-space layer, block-sparse and linear attention each have their
own) are a GROUP: their parameters are
stacked on a leading dim and the forward pass is one ``lax.scan`` a
group (one compiled body however many layers; ``remat`` is
``jax.checkpoint`` of that body with ``llama.py``'s policy
``REMAT_SAVED``, which saves beside the layer's input the experts
selected: a selection made again from recomputed scores need not be the
one the forward pass used; the flash kernel's output and row
statistics, ``B*T*H*Dh`` elements of ``dtype`` + ``B*H*T*4`` bytes a
layer, 68.2 MB a sequence of 8,192 at 32 x 128 heads; the up
product of the dense FFN or the shared expert, ``B*T*F`` elements of
``dtype``, 100.7 MB at F = 6,144; the residual stream after the
mixer's add (``layer.mid``, ``B*T*D`` elements, 33.6 MB at D = 2,048,
named in :func:`apply_block`); and a state-space layer's input
projection (``ssm.in``, ``B*T*proj_dim`` elements, 139.5 MB at 8,512,
named in :func:`mamba2.apply_mixer`): the backward pass runs the rest
of the layer's forward again, the FFN's gate product too, but not the
attention kernel, the up product, a state-space layer's ``W_in`` nor
the mixer's output projection, whose product only the kept stream
read (where ``post_norms`` puts a norm on that product, the norm's
backward reads it and it runs again); the routed experts keep what
their hand-written backward keeps).
Where a group mixes attention kinds the body picks the layer's kernel
with a ``cond`` on a scanned flag (the flash kernel's ``window`` is
static); the branches' residuals are of one shape and share the
``cond``'s outputs, so such a layer pays the bytes once.
``params["layers"]`` is the list of groups; an adapter tree from
:func:`rayfed_tpu.models.lora.init_lora` mirrors it with the group's
index as a string.  :func:`unstack` gives either tree layer by layer.

Five configurations run through it: the AFMoE family's
(``benchmark/families/afmoe_lm.py``, reference in
``benchmark/reference/afmoe.py``), the ``kimi_k2`` / DeepSeek-V3
block (``benchmark/families/kimi_k2_lm.py``, ``reference/kimi_k2.py``),
all of whose layers are latent, the ``granitemoehybrid`` block
(``benchmark/families/granite_hybrid_lm.py``,
``reference/granite_hybrid.py``): nine state-space layers to one of
full attention without positions, a dense FFN after each, and the
``nemotron_h`` block (``benchmark/families/nemotron_h_lm.py``,
``reference/nemotron_h.py``): single-part Mamba-2, latent-expert and
attention blocks, and an MTP module; and the ``minicpm_sala`` block
(``benchmark/families/minicpm_sala_lm.py``, ``reference/minicpm_sala.py``):
one block-sparse layer to three of linear attention, a dense FFN after
each.  Helpers are shared with
``llama.py`` by import (``_rms_norm``, ``rope_tables``, ``apply_rope``,
``_linear``, ``lm_loss``, ``frozen_head_loss``, ``adam_part``,
``embed_part``);
``llama.py``'s own programs do not pass through this module.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from rayfed_tpu import telemetry
from rayfed_tpu.models import mamba2, moe
from rayfed_tpu.models.llama import (
    HEAD_LOSS_SCOPE,
    LAYER_MID_NAME,
    REMAT_SAVED,
    YarnScaling,
    _linear,
    _rms_norm,
    adam_part,
    apply_rope,
    checkpoint_layer,
    embed_part,
    emit_remat_saved,
    frozen_head_loss,
    lm_loss,  # noqa: F401  (benchmark/families read `decoder.lm_loss`)
    remat_saved_bytes,
    rope_tables,
)
from rayfed_tpu.models.mamba2 import SsmConfig
from rayfed_tpu.ops import sparse_attention as sparse
from rayfed_tpu.ops.attention import dot_product_attention
from rayfed_tpu.ops.sparse_attention import SparseConfig
from rayfed_tpu.ops.ssd import ssd_scan

Params = Dict[str, Any]

# Every linear matrix of the block but the router (LoraConfig.targets):
# wq wk wv wo and the output gate wz, a latent layer's five (wq_a wq_b
# wkv_a wkv_b wo), or a state-space layer's two (w_in w_out); the dense
# FFN's, the shared expert's and each held expert's three (two where
# they are squared-ReLU), an expert layer's latent pair (w_lat_in
# w_lat_out) and the MTP module's projection (w_eh).
ALL_LINEAR = (r"/w([qkvoz]|q_[ab]|kv_[ab])$",
              r"/w_(gate|up|down|in|out|lat_in|lat_out|eh)$")

# A mixer's kind -> the kind of its PARAMETERS: layers stack into one
# scanned group only where these agree.
MIXER_PARAMS = {
    "window": "attention", "full": "attention", "latent": "latent",
    "ssm": "ssm", "sparse": "sparse", "lightning": "lightning",
}
FFN_KINDS = ("dense", "moe", "none")
# The scopes of the MTP module on a device trace: all of it, and its
# input (the two norms, the concatenation and ``w_eh``).
MTP_SCOPE, MTP_FUSE_SCOPE = "mtp", "mtp.fuse"


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    # "window" (with RoPE) | "full" (no positions) | "latent" (RoPE on a
    # part of the head, DecoderConfig.latent) | "ssm" (no attention: the
    # Mamba-2 mixer, DecoderConfig.ssm) | "sparse" (no positions, each
    # query over its selected key blocks, DecoderConfig.sparse) |
    # "lightning" (decayed linear attention, DecoderConfig.lightning)
    mixer: str = "window"
    ffn: str = "dense"  # "dense" | "moe" | "none" (the mixer alone)

    def __post_init__(self):
        if self.mixer not in MIXER_PARAMS:
            raise ValueError(
                f"unknown mixer kind {self.mixer!r}: one of "
                f"{', '.join(MIXER_PARAMS)}"
            )
        if self.ffn not in FFN_KINDS:
            raise ValueError(
                f"unknown ffn kind {self.ffn!r}: one of {', '.join(FFN_KINDS)}"
            )

    @property
    def attention(self) -> str:
        """The mixer under the name the field had while every mixer was
        attention: read-only, for ``benchmark/families/afmoe_lm.py``."""
        return self.mixer


@dataclasses.dataclass(frozen=True)
class LatentConfig:
    """The widths of a latent-attention layer: the ranks of the query
    and key/value latents, and per head the part of the query-key width
    that carries no position (``nope_dim``), the rotary part
    (``rope_dim``) and the value width."""

    q_rank: int = 1536
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128


# The MTP module's loss enters the step's loss times this: Megatron-Core's
# ``mtp_loss_scaling_factor`` default (a model's config does not state it).
MTP_LOSS_WEIGHT = 0.1


@dataclasses.dataclass(frozen=True)
class LightningConfig:
    """A decayed linear-attention layer (Lightning Attention-2,
    arXiv:2401.04658; MiniMax-01, arXiv:2501.08313): a head's state
    decays by ``exp(-r_h)`` a token, ``r_h = 2 ** (-8 (h + 1) / H) (1 -
    l / (depth - 1) + 1e-5)`` for layer ``l`` of a model ``depth``
    layers deep (MiniMax-01's ALiBi-style slopes; ``H`` a power of two),
    and the scan runs in chunks of ``chunk`` tokens
    (:func:`rayfed_tpu.ops.ssd.ssd_scan`, one group a head)."""

    depth: int = 32  # the published layers the slopes' depth factor reads
    chunk: int = 256

    def decay_rates(self, layer: int, heads: int):
        """``r_h`` [heads] float32 of layer ``layer``."""
        import numpy as np

        if heads & (heads - 1):
            raise ValueError(f"{heads} heads: the slopes need a power of two")
        slopes = 2.0 ** (-8.0 * np.arange(1, heads + 1) / heads)
        depth = 1.0 - layer / max(self.depth - 1, 1) + 1e-5
        return jnp.asarray(slopes * depth, jnp.float32)


@dataclasses.dataclass(frozen=True)
class MtpConfig:
    """A multi-token-prediction module after the layers: its own layers
    (their widths are the decoder's)."""

    layers: Tuple[LayerSpec, ...]


def _groups_of(layers, offset: int = 0) -> Tuple[Tuple[int, int], ...]:
    stacks = lambda s: (s.ffn, MIXER_PARAMS[s.mixer])
    out, start = [], 0
    for i in range(1, len(layers) + 1):
        if i == len(layers) or stacks(layers[i]) != stacks(layers[start]):
            out.append((offset + start, offset + i))
            start = i
    return tuple(out)


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    layers: Tuple[LayerSpec, ...]
    vocab_size: int = 25024
    hidden_size: int = 2048
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    intermediate_size: int = 6144  # the dense FFN's width
    sliding_window: int = 2048
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    rope_scaling: Optional[YarnScaling] = None
    latent: Optional[LatentConfig] = None  # a "latent" layer's widths
    ssm: Optional[SsmConfig] = None  # an "ssm" layer's widths
    sparse: Optional[SparseConfig] = None  # a "sparse" layer's sizes
    lightning: Optional[LightningConfig] = None  # a "lightning" layer's
    # The block's optional parts.
    qk_norm: bool = True  # RMS norm of q and k over the head width
    output_gate: bool = True  # attention output * sigmoid(x wz)
    post_norms: bool = True  # a norm on each sub-block's output
    embed_scale: float = 1.0  # the residual stream starts at embed * this
    residual_scale: float = 1.0  # x + this * sub_block(norm(x)), both
    # scores times this; None: the score width ** -0.5 (the kernels' own)
    attn_scale: Optional[float] = None
    logit_scale: float = 1.0  # logits times this
    tie_embeddings: bool = False  # the head is the embedding, transposed
    experts: Optional[moe.ExpertShareConfig] = None
    mtp: Optional[MtpConfig] = None  # a multi-token-prediction module
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False

    def __post_init__(self):
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_heads must be a multiple of num_kv_heads")
        if any(s.ffn == "moe" for s in self.stack) and self.experts is None:
            raise ValueError("a layer with ffn='moe' needs config.experts")
        for kind in ("latent", "ssm", "sparse", "lightning"):
            if getattr(self, kind) is None and any(
                s.mixer == kind for s in self.stack
            ):
                raise ValueError(
                    f"a layer with mixer={kind!r} needs config.{kind}"
                )

    @property
    def stack(self) -> Tuple[LayerSpec, ...]:
        """Every layer, the MTP module's after the decoder's: what a
        layer index counts (``aux``, ``moe.counts``, a group's scope)."""
        return self.layers + (self.mtp.layers if self.mtp else ())

    def groups(self) -> Tuple[Tuple[int, int], ...]:
        """``(first layer, one past the last)`` of every run of
        consecutive layers with one FFN kind and one kind of mixer
        parameters (``MIXER_PARAMS``): what one scan can stack."""
        return _groups_of(self.layers)

    def mtp_groups(self) -> Tuple[Tuple[int, int], ...]:
        """The MTP module's groups, by their index in :attr:`stack`."""
        return _groups_of(self.mtp.layers, len(self.layers)) if self.mtp else ()


def init_decoder(key: jax.Array, config: DecoderConfig) -> Params:
    """Random weights: normal ``fan_in ** -0.5`` (the embedding 0.02, the
    published initializer range, so that the scaled embedding is of the
    size the sub-blocks' normed outputs add to), norms at one; a
    state-space layer's buffers as :func:`mamba2.init_mixer` draws them.
    Every layer draws from a key of its own; a group's layers are
    stacked.  No ``lm_head`` where the head is tied to the embedding.
    The MTP module (``params["mtp"]``) draws from a key of its own: its
    two norms, ``w_eh`` [2 D, D], its layers as the decoder's, its final
    norm."""
    c = config
    d, dh, h, kv = c.hidden_size, c.head_dim, c.num_heads, c.num_kv_heads
    pdt = c.param_dtype
    k_embed, k_head, k_layers = jax.random.split(key, 3)

    def dense(key, *shape, fan_in):
        return (jax.random.normal(key, shape) * fan_in**-0.5).astype(pdt)

    def layer(key, spec: LayerSpec, index: int) -> Params:
        ks = jax.random.split(key, 9)
        ones = lambda n: jnp.ones((n,), pdt)
        # `attn_norm` is the norm before the mixer, whatever the mixer
        lp = {"attn_norm": ones(d)}
        if spec.ffn != "none":
            lp["mlp_norm"] = ones(d)
        if spec.mixer == "ssm":
            lp.update(mamba2.init_mixer(ks[0], d, c.ssm, pdt))
        elif spec.mixer == "latent":
            m = c.latent
            lp.update(
                wq_a=dense(ks[0], d, m.q_rank, fan_in=d),
                q_a_norm=ones(m.q_rank),
                # a head: [nope | rope]
                wq_b=dense(ks[1], m.q_rank, h * (m.nope_dim + m.rope_dim),
                           fan_in=m.q_rank),
                # [the key/value latent | the one rotary key head]
                wkv_a=dense(ks[2], d, m.kv_rank + m.rope_dim, fan_in=d),
                kv_a_norm=ones(m.kv_rank),
                # a head: [k_nope | v]
                wkv_b=dense(ks[3], m.kv_rank, h * (m.nope_dim + m.v_dim),
                            fan_in=m.kv_rank),
                wo=dense(ks[4], h * m.v_dim, d, fan_in=h * m.v_dim),
            )
        else:
            # a linear-attention layer's keys and values have a head each
            heads_kv = h if spec.mixer == "lightning" else kv
            lp.update(
                wq=dense(ks[0], d, h * dh, fan_in=d),
                wk=dense(ks[1], d, heads_kv * dh, fan_in=d),
                wv=dense(ks[2], d, heads_kv * dh, fan_in=d),
                wo=dense(ks[4], h * dh, d, fan_in=h * dh),
            )
            if c.qk_norm:
                lp.update(q_norm=ones(dh), k_norm=ones(dh))
            if spec.mixer == "lightning":
                # the output's norm, a head at a time; the state's decay
                # rates (a buffer, float32 whatever the parameter type)
                lp.update(o_norm=ones(h * dh),
                          decay=c.lightning.decay_rates(index, h))
        if c.output_gate and spec.mixer != "ssm":
            lp["wz"] = dense(ks[3], d, lp["wo"].shape[0], fan_in=d)
        if c.post_norms:
            lp.update(post_attn_norm=ones(d), post_mlp_norm=ones(d))
        if spec.ffn == "dense":
            f = c.intermediate_size
            lp["w_gate"] = dense(ks[5], d, f, fan_in=d)
            lp["w_up"] = dense(ks[6], d, f, fan_in=d)
            lp["w_down"] = dense(ks[7], f, d, fan_in=f)
        elif spec.ffn == "moe":
            lp["moe"] = moe.init_expert_share(ks[8], c.experts, pdt)
        return lp

    stack = lambda *leaves: jnp.stack(leaves)

    def stacked(keys, layers, groups, offset=0):
        return [
            jax.tree_util.tree_map(stack, *(
                layer(keys[i - offset], layers[i - offset], i)
                for i in range(start, stop)
            ))
            for start, stop in groups
        ]

    params = {
        "embed": (jax.random.normal(k_embed, (c.vocab_size, d)) * 0.02
                  ).astype(pdt),
        "layers": stacked(
            jax.random.split(k_layers, len(c.layers)), c.layers, c.groups()
        ),
        "final_norm": jnp.ones((d,), pdt),
    }
    if not c.tie_embeddings:
        params["lm_head"] = dense(k_head, d, c.vocab_size, fan_in=d)
    if c.mtp:
        k_eh, k_mtp = jax.random.split(jax.random.fold_in(key, 2))
        params["mtp"] = {
            "enorm": jnp.ones((d,), pdt),
            "hnorm": jnp.ones((d,), pdt),
            "w_eh": dense(k_eh, 2 * d, d, fan_in=2 * d),
            "layers": stacked(
                jax.random.split(k_mtp, len(c.mtp.layers)), c.mtp.layers,
                c.mtp_groups(), len(c.layers),
            ),
            "final_norm": jnp.ones((d,), pdt),
        }
    return params


def unstack(tree: Params, config: DecoderConfig) -> Params:
    """``tree`` (parameters, or adapters mirroring them) with ``layers``
    layer by layer: a list of per-layer dicts for parameters, a dict
    keyed by the layer's index as a string for adapters.  What a reader
    that knows nothing of groups takes (the plain reference).  The MTP
    module's layers alike, indexed from 0 within it."""
    out = _unstack_layers(tree, config.groups())
    if config.mtp and tree.get("mtp"):
        out["mtp"] = _unstack_layers(
            tree["mtp"], _groups_of(config.mtp.layers)
        )
    return out


def _unstack_layers(tree, group_bounds):
    groups = tree.get("layers", {})
    of_group = (lambda g: groups[g]) if isinstance(groups, list) else (
        lambda g: groups.get(str(g), {})
    )
    layers = {}
    for g, (start, stop) in enumerate(group_bounds):
        for i in range(start, stop):
            layers[i] = jax.tree_util.tree_map(
                # an adapter's `scale` is one number for the whole group
                lambda leaf: leaf[i - start] if jnp.ndim(leaf) else leaf,
                of_group(g),
            )
    if isinstance(groups, list):
        return dict(tree, layers=[layers[i] for i in sorted(layers)])
    return dict(tree, layers={str(i): v for i, v in layers.items() if v})


def embed(params: Params, input_ids: jax.Array, config: DecoderConfig):
    """``[B, T]`` ids -> the residual stream before layer 0."""
    return embed_part(
        params["embed"], input_ids, dtype=jnp.dtype(config.dtype),
        scale=float(config.embed_scale),
    )


def _attend(q, k, v, rope, *, kind, config, attn_fn):
    # a score scale of the configuration's own goes to the kernel; None
    # leaves it the kernel's default, as every call before there was one
    scaled = {} if config.attn_scale is None else {
        "sm_scale": config.attn_scale
    }
    with jax.named_scope(f"attn.{kind}"):
        if kind == "full":
            return attn_fn(q, k, v, causal=True, **scaled)
        if kind == "latent":
            # Rotary positions on the rotary part alone; the score's two
            # parts go to the kernel as they are (the shared rotary key
            # is never copied to the heads, nor V padded to the
            # query-key width: ops.flash_attention).
            (q_nope, q_pe), (k_nope, k_pe) = q, k
            m, scaling = config.latent, config.rope_scaling
            scale = (m.nope_dim + m.rope_dim) ** -0.5 * (
                scaling.softmax_scale() if scaling else 1.0
            )
            return attn_fn(
                (q_nope, apply_rope(q_pe, *rope)),
                (k_nope, apply_rope(k_pe, *rope)),
                v, causal=True, sm_scale=scale,
            )
        q, k = apply_rope(q, *rope), apply_rope(k, *rope)
        return attn_fn(q, k, v, causal=True, window=config.sliding_window,
                       **scaled)


def _latent_qkv(y, lp, config: DecoderConfig, lget):
    """A latent layer's heads from the normed stream ``y`` [B, T, D]:
    ``(q_nope, q_pe)`` [B, T, H, nope | rope], ``(k_nope, k_pe)`` with
    ``k_pe`` [B, T, 1, rope] the one rotary key head, ``v`` [B, T, H,
    v_dim]."""
    c, m = config, config.latent
    b, t, _ = y.shape
    h, dtype = c.num_heads, c.dtype
    c_q = _linear(y, lp["wq_a"], lget("wq_a"), dtype)
    c_q = _rms_norm(c_q, lp["q_a_norm"], c.rms_eps)
    q = _linear(c_q, lp["wq_b"], lget("wq_b"), dtype)
    q = q.reshape(b, t, h, m.nope_dim + m.rope_dim)
    kv_a = _linear(y, lp["wkv_a"], lget("wkv_a"), dtype)
    c_kv = _rms_norm(kv_a[..., : m.kv_rank], lp["kv_a_norm"], c.rms_eps)
    k_pe = kv_a[..., m.kv_rank:].reshape(b, t, 1, m.rope_dim)
    kv = _linear(c_kv, lp["wkv_b"], lget("wkv_b"), dtype)
    kv = kv.reshape(b, t, h, m.nope_dim + m.v_dim)
    return (
        (q[..., : m.nope_dim], q[..., m.nope_dim:]),
        (kv[..., : m.nope_dim], k_pe),
        kv[..., m.nope_dim:],
    )


def _add(x, branch, scale: float):
    """``x + scale * branch``; a scale that is not 1 in float32 (0.22
    has no bf16: rounded first it would bias every layer alike)."""
    if scale == 1.0:
        return x + branch
    f32 = jnp.float32
    return (x.astype(f32) + branch.astype(f32) * scale).astype(x.dtype)


def apply_block(x, lp, config: DecoderConfig, *, ffn: str, mixer=None,
                attention=None, attn_fn: Callable = dot_product_attention,
                lora: Optional[Params] = None):
    """One layer on the stream ``x`` [B, T, D] -> (``x``, ``aux``).
    ``lp`` (and ``lora``) are ONE layer's entries; ``mixer``
    (or ``attention=``, its older name: one of the two) is the layer's kind,
    or a traced boolean "is windowed" (both kernels are then in the
    program, under a ``cond``); ``aux`` is what
    :func:`moe.apply_expert_share` reports, None for a dense FFN or
    none (``ffn="none"``: the layer is its mixer alone)."""
    c = config
    lget = (lora or {}).get
    if (mixer is None) == (attention is None):
        raise TypeError("apply_block takes mixer= (or attention=, not both)")
    kind = attention if mixer is None else mixer
    mixed = None  # what the mixer reports (a block-sparse layer's choice)
    if isinstance(kind, str) and kind == "ssm":
        with jax.named_scope("ssm.proj"):
            y = _rms_norm(x, lp["attn_norm"], c.rms_eps)
        o = mamba2.apply_mixer(y, lp, c.ssm, lget, c.dtype, c.rms_eps)
        with jax.named_scope("ssm.proj"):
            if c.post_norms:
                o = _rms_norm(o, lp["post_attn_norm"], c.rms_eps)
            x = _add(x, o, c.residual_scale)
    elif isinstance(kind, str) and kind == "lightning":
        x = _lightning_block(x, lp, c, lget)
    else:
        x, mixed = _attention_block(x, lp, c, kind, attn_fn, lget)
    if ffn == "none":
        return x, mixed
    # kept by a checkpointed layer: its second forward starts here
    x = checkpoint_name(x, LAYER_MID_NAME)
    b, t, _ = x.shape
    y = _rms_norm(x, lp["mlp_norm"], c.rms_eps)
    aux = None
    if ffn == "dense":
        with jax.named_scope("ffn.dense"):
            f = moe.swiglu(y, lp, lget, c.dtype)
    else:
        f, aux = moe.apply_expert_share(
            lp["moe"], y.reshape(b * t, -1), c.experts, lora=lget("moe"),
        )
        f = f.reshape(b, t, -1)
    if c.post_norms:
        f = _rms_norm(f, lp["post_mlp_norm"], c.rms_eps)
    if mixed is not None:
        aux = dict(mixed, **(aux or {}))
    return _add(x, f, c.residual_scale), aux


def _attention_block(x, lp, config: DecoderConfig, attention, attn_fn, lget):
    """``x`` plus an attention layer's first sub-block: norm,
    projections, the kernel of its kind, gate, output projection; and
    what a block-sparse layer reports of its selection (None for
    another kind, or at a length it attends densely)."""
    c = config
    b, t, _ = x.shape
    h, kv, dh, dtype = c.num_heads, c.num_kv_heads, c.head_dim, c.dtype
    latent = isinstance(attention, str) and attention == "latent"
    rope = rope_tables(
        jnp.arange(t), c.latent.rope_dim if latent else dh, c.rope_theta,
        c.rope_scaling,
    )
    with jax.named_scope("attn.proj"):
        y = _rms_norm(x, lp["attn_norm"], c.rms_eps)
        if latent:
            q, k, v = _latent_qkv(y, lp, c, lget)
        else:
            q = _linear(y, lp["wq"], lget("wq"), dtype).reshape(b, t, h, dh)
            k = _linear(y, lp["wk"], lget("wk"), dtype).reshape(b, t, kv, dh)
            v = _linear(y, lp["wv"], lget("wv"), dtype).reshape(b, t, kv, dh)
            if c.qk_norm:
                q = _rms_norm(q, lp["q_norm"], c.rms_eps)
                k = _rms_norm(k, lp["k_norm"], c.rms_eps)
    attend = functools.partial(_attend, config=c, attn_fn=attn_fn)
    mixed = None
    if isinstance(attention, str) and attention == "sparse":
        o, mixed = _sparse_attend(q, k, v, c, attn_fn)
    elif isinstance(attention, str):
        o = attend(q, k, v, rope, kind=attention)
    else:
        o = jax.lax.cond(
            attention, functools.partial(attend, kind="window"),
            functools.partial(attend, kind="full"), q, k, v, rope,
        )
    with jax.named_scope("attn.proj"):
        o = o.reshape(b, t, -1)
        if c.output_gate:
            z = _linear(y, lp["wz"], lget("wz"), dtype)
            o = (o * jax.nn.sigmoid(z.astype(jnp.float32))).astype(dtype)
        o = _linear(o, lp["wo"], lget("wo"), dtype)
        if c.post_norms:
            o = _rms_norm(o, lp["post_attn_norm"], c.rms_eps)
        return _add(x, o, c.residual_scale), mixed


def _sparse_attend(q, k, v, config: DecoderConfig, attn_fn):
    """A block-sparse layer's attention (no positions) and its report:
    up to ``dense_len`` tokens causal attention through ``attn_fn``
    (``attn.sparse``, no report); beyond, the selection
    (``attn.select``: compressed keys, scores, the top-k mask, no
    gradient) and the kernel over the selected blocks
    (``attn.sparse``), reporting ``selected`` [B, KV, T, topk] (each
    query's blocks in ascending order, -1 where fewer), the mean causal
    keys and blocks a query visits, the mean (query tile, key tile)
    pairs the kernels walk a K/V head, and the shares of (query, K/V
    head) rows where the block index broke a tie at the threshold
    (``tie_rows``) and with fewer than ``topk`` causal blocks
    (``short_rows``)."""
    s = config.sparse
    scaled = {} if config.attn_scale is None else {"sm_scale": config.attn_scale}
    t = q.shape[1]
    if t <= s.dense_len:
        with jax.named_scope("attn.sparse"):
            return attn_fn(q, k, v, causal=True, **scaled), None
    with jax.named_scope("attn.select"):
        mask, tied = sparse.select_mask(
            jax.lax.stop_gradient(q), jax.lax.stop_gradient(k), s
        )
        arrays = sparse.selection_arrays(mask, t, s)
        keys, blocks = sparse.visit_stats(mask, s.block_size)
        pairs = jnp.mean(arrays[1][2].astype(jnp.float32))
        # every causal block is selected where fewer than topk exist
        short = jnp.mean(jnp.sum(mask, -1) < s.topk, dtype=jnp.float32)
        report = {
            "selected": sparse.mask_indices(mask, min(s.topk, mask.shape[-1])),
            "visited_keys": keys, "blocks": blocks, "pairs": pairs,
            "tie_rows": jnp.mean(tied, dtype=jnp.float32), "short_rows": short,
        }
    with jax.named_scope("attn.sparse"):
        o = sparse.sparse_attention(q, k, v, arrays, s, **scaled)
    return o, report


def _lightning_block(x, lp, config: DecoderConfig, lget):
    """``x`` plus a decayed linear-attention layer's first sub-block:
    norm; ``q``, ``k``, ``v`` a head each, RMS-normed (``q``, ``k``) and
    rotated over the whole head; per head ``S_t = exp(-r_h) S_{t-1} +
    k_t v_t^T``, ``o_t = (q_t / sqrt(d))^T S_t`` (``attn.lightning``:
    :func:`ssd_scan` with ``dt = 1``, ``A = -r``, ``B = k``, ``C = q /
    sqrt(d)``, ``x = v``, ``D = 0``, one group a head); the output
    RMS-normed a head at a time, gated, projected."""
    c = config
    b, t, _ = x.shape
    h, dh, dtype = c.num_heads, c.head_dim, c.dtype
    rope = rope_tables(jnp.arange(t), dh, c.rope_theta, c.rope_scaling)
    with jax.named_scope("attn.proj"):
        y = _rms_norm(x, lp["attn_norm"], c.rms_eps)
        q, k, v = (
            _linear(y, lp[name], lget(name), dtype).reshape(b, t, h, dh)
            for name in ("wq", "wk", "wv")
        )
        if c.qk_norm:
            q = _rms_norm(q, lp["q_norm"], c.rms_eps)
            k = _rms_norm(k, lp["k_norm"], c.rms_eps)
        # the score scale rides q's rotation: no pass of its own
        q = apply_rope(q, *(table * dh ** -0.5 for table in rope))
        k = apply_rope(k, *rope)
    with jax.named_scope("attn.lightning"):
        o = ssd_scan(
            v, jnp.ones((b, t, h), jnp.float32),
            -lp["decay"].astype(jnp.float32), k, q,
            jnp.zeros((h,), jnp.float32), chunk=c.lightning.chunk,
        )
    with jax.named_scope("attn.proj"):
        o = _rms_norm(o, lp["o_norm"].reshape(h, dh), c.rms_eps)
        o = o.reshape(b, t, -1)
        if c.output_gate:
            z = _linear(y, lp["wz"], lget("wz"), dtype)
            o = (o * jax.nn.sigmoid(z.astype(jnp.float32))).astype(dtype)
        o = _linear(o, lp["wo"], lget("wo"), dtype)
        if c.post_norms:
            o = _rms_norm(o, lp["post_attn_norm"], c.rms_eps)
        return _add(x, o, c.residual_scale)


def _split_scalars(tree):
    """``(arrays, rebuild)``: the tree's leaves that have a leading dim
    to scan over, and ``rebuild(arrays)`` putting the scalars (an
    adapter's ``scale``) back between them."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    scalar = [jnp.ndim(leaf) == 0 for leaf in leaves]

    def rebuild(arrays):
        it = iter(arrays)
        return treedef.unflatten([
            leaf if is_scalar else next(it)
            for leaf, is_scalar in zip(leaves, scalar)
        ])

    return [l for l, s in zip(leaves, scalar) if not s], rebuild


def apply_decoder(
    params: Params,
    input_ids: jax.Array,
    config: DecoderConfig,
    *,
    lora: Optional[Params] = None,
    attn_fn: Callable = dot_product_attention,
    last: Optional[int] = None,
):
    """``[B, T]`` ids -> (``[B, T, V]`` float32 logits, ``aux``); with
    ``last`` the logits of the last ``last`` positions alone (a
    vocabulary of 100,352 makes all 8,192 positions' 3.3 GB).

    ``aux`` maps each expert layer's index to what
    :func:`moe.apply_expert_share` reports (``counts``,
    ``held_assignments``, ``selected``, ``scores``).  ``lora`` mirrors
    ``params`` (what :func:`rayfed_tpu.models.lora.init_lora` makes of
    them: ``layers`` keyed by the group's index as a string).
    """
    c = config
    x, aux = _hidden_states(params, input_ids, c, lora, attn_fn)
    if last is not None:
        x = x[:, -last:]
    head, rows = _head(params, c)
    logits = jax.lax.dot_general(
        x, head, (((2,), (rows,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if c.logit_scale != 1.0:
        logits = logits * c.logit_scale
    return logits, aux


def _head(params, config: DecoderConfig):
    """``(matrix, the dim of it that is the model width)``: ``lm_head``
    [D, V], or the tied embedding [V, D] as it lies (no transposed
    copy)."""
    if config.tie_embeddings:
        return params["embed"].astype(config.dtype), 1
    return params["lm_head"].astype(config.dtype), 0


def _hidden_states(params, input_ids, config, lora, attn_fn):
    """The final-normed residual stream [B, T, D] in the compute dtype,
    and ``apply_decoder``'s ``aux``."""
    _, x, aux = streams(params, input_ids, config, lora, attn_fn)
    return _final_norm(x, params["final_norm"], config), aux


def _final_norm(x, scale, config):
    with jax.named_scope(HEAD_LOSS_SCOPE):
        x = _rms_norm(x, scale, config.rms_eps)
    return x.astype(config.dtype)


def streams(params, input_ids, config: DecoderConfig, lora=None,
            attn_fn: Callable = dot_product_attention):
    """``(the embedded ids, the stream after the last layer before the
    final norm, aux)``: what :func:`mtp_fuse` reads."""
    c = config
    x0 = embed(params, input_ids, c)
    aux = {}
    x = _run_groups(params["layers"], x0, c, c.groups(),
                    (lora or {}).get("layers", {}), attn_fn, aux)
    return x0, x, aux


def _run_groups(groups, x, config, bounds, lora_groups, attn_fn, aux):
    """The stream ``x`` through the scanned groups ``groups`` (``bounds``
    their layers' indices in ``config.stack``); each expert layer's
    report goes into ``aux`` under its index."""
    c = config
    for g, (start, stop) in enumerate(bounds):
        specs = c.stack[start:stop]
        kinds = {s.mixer for s in specs}
        adapters, rebuild = _split_scalars(lora_groups.get(str(g)))

        def body(x, scanned, specs=specs, kinds=kinds, rebuild=rebuild):
            lp, adapters, windowed = scanned
            return apply_block(
                x, lp, c, ffn=specs[0].ffn, attn_fn=attn_fn,
                mixer=specs[0].mixer if len(kinds) == 1 else windowed,
                lora=rebuild(adapters),
            )

        if c.remat:
            body = checkpoint_layer(body, REMAT_SAVED, stop - start)
        windowed = jnp.asarray([s.mixer == "window" for s in specs])
        with jax.named_scope(f"layers{start}-{stop - 1}"):
            x, stacked = jax.lax.scan(
                body, x, (groups[g], adapters, windowed),
            )
        if stacked is not None:
            for i in range(start, stop):
                aux[i] = jax.tree_util.tree_map(lambda a: a[i - start], stacked)
    return x


def mtp_fuse(params, x0, h, config: DecoderConfig, lora=None):
    """The MTP module's input [B, T, D]: position ``i`` reads the
    embedding of token ``i + 1`` (``x0``, the embedded ids, rolled: the
    last position reads the first token's and has no target) beside the
    last layer's output ``h`` at ``i``, each normed, concatenated and
    projected by ``w_eh``."""
    c, p = config, params["mtp"]
    with jax.named_scope(MTP_FUSE_SCOPE):
        e = _rms_norm(jnp.roll(x0, -1, axis=1), p["enorm"], c.rms_eps)
        y = jnp.concatenate([e, _rms_norm(h, p["hnorm"], c.rms_eps)], -1)
        return _linear(y, p["w_eh"], (lora or {}).get("w_eh"), c.dtype)


def routing_counts(aux) -> Optional[jax.Array]:
    """``[expert layers, held + 1]`` int32: every expert layer's rows per
    held expert, and in the last column its held assignments."""
    rows = [
        jnp.concatenate([a["counts"], a["held_assignments"][None]])
        for _, a in sorted(aux.items()) if "counts" in a
    ]
    return jnp.stack(rows) if rows else None


_SELECTION_STATS = ("visited_keys", "blocks", "pairs", "tie_rows",
                    "short_rows")


def selection_stats(aux) -> Optional[jax.Array]:
    """``[block-sparse layers, 5]`` float32: every such layer's mean
    causal keys and selected blocks a query, tile pairs its kernels walk
    a K/V head, and shares of rows the tie-break decided and with fewer
    than ``topk`` causal blocks; None where no layer selected."""
    rows = [
        jnp.stack([a[name] for name in _SELECTION_STATS])
        for _, a in sorted(aux.items()) if "visited_keys" in a
    ]
    return jnp.stack(rows) if rows else None


def _kept_by_group(config: DecoderConfig, shape):
    """``{scanned group: (layers, {name: bytes a layer keeps})}`` by
    ``llama.remat_saved_bytes`` at the widths of the group's layers, for
    ids of ``shape`` [B, T]; empty without ``remat``."""
    c, kept = config, {}
    (batch, t), tokens = shape, shape[0] * shape[1]
    for start, stop in c.groups() + c.mtp_groups() if c.remat else ():
        spec = c.stack[start]
        routed = spec.ffn == "moe"
        selects = spec.mixer == "sparse" and t > c.sparse.dense_len
        kept[f"layers{start}-{stop - 1}"] = (stop - start, remat_saved_bytes(
            tokens, c.dtype, hidden=c.hidden_size,
            # the dense FFN's or the shared expert's up product, if any
            ffn_up=c.intermediate_size if spec.ffn == "dense" else (
                c.experts.shared_width if routed else 0
            ),
            top_k=c.experts.top_k if routed else 0,
            ssm_in=c.ssm.proj_dim if spec.mixer == "ssm" else 0,
            selection=sparse.selection_bytes(
                batch, t, c.num_kv_heads, c.sparse
            ) if selects else 0,
        ))
    return kept


def lora_loss(lora, base, ids, config: DecoderConfig, *,
              attn_fn: Callable = dot_product_attention):
    """Next-token loss of ``ids`` [B, T] with adapters ``lora`` on the
    frozen ``base``, and ``apply_decoder``'s ``aux``: what the LoRA step
    differentiates.  The head is frozen too, so head and loss are fused
    (:func:`llama.frozen_head_loss`: no ``[B, T, V]`` array).  With an
    MTP module the loss is :func:`lora_loss_terms`' total."""
    loss, aux, _ = lora_loss_terms(lora, base, ids, config, attn_fn=attn_fn)
    return loss, aux


def lora_loss_terms(lora, base, ids, config: DecoderConfig, *,
                    attn_fn: Callable = dot_product_attention):
    """``(loss, aux, terms)``: :func:`lora_loss` and, with an MTP module,
    ``terms = (main, mtp)`` where ``loss = main + MTP_LOSS_WEIGHT * mtp`` and
    ``mtp`` is the module's loss on the token after next (the last two
    positions have no target); ``aux`` then holds the module's expert
    layers too.  ``terms`` is None without one."""
    c = config
    x0, h, aux = streams(base, ids, c, lora, attn_fn)
    x = _final_norm(h, base["final_norm"], c)
    emit_remat_saved(_kept_by_group(c, ids.shape), ids.size, c.vocab_size)
    head, rows = _head(base, c)
    scale = None if c.logit_scale == 1.0 else jnp.float32(c.logit_scale)
    loss = frozen_head_loss(x, head, ids, scale, head_rows=bool(rows))
    if not c.mtp:
        return loss, aux, None
    with jax.named_scope(MTP_SCOPE):
        lm = (lora or {}).get("mtp") or {}
        y = _run_groups(base["mtp"]["layers"], mtp_fuse(base, x0, h, c, lm),
                        c, c.mtp_groups(), lm.get("layers", {}), attn_fn, aux)
        y = _final_norm(y, base["mtp"]["final_norm"], c)
        mtp = frozen_head_loss(y, head, ids, scale, head_rows=bool(rows),
                               shift=2)
    return loss + jnp.float32(MTP_LOSS_WEIGHT) * mtp, aux, (loss, mtp)


def make_lora_train_step(
    config: DecoderConfig,
    lr: float = 1e-4,
    *,
    attn_fn: Callable = dot_product_attention,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
):
    """Adam step over LoRA adapters only; base, router and selection
    bias frozen.  ``(lora, opt, base, ids) -> (lora, opt, loss,
    counts)`` with ``counts`` as :func:`routing_counts` gives them (None
    for a decoder with no expert layer).  The jitted program is
    ``step.jitted`` (``jit_decoder_lora_step`` on a device trace); it
    also returns the MTP module's loss terms (None without one).

    While the flight recorder is armed the step keeps each call's
    counts, loss terms and block-sparse layers' visits, still on the
    device, and ``step.flush_routing()`` writes one ``moe.counts`` record
    (with an MTP module also one ``mtp.loss`` record, with a block-sparse
    layer that selects one ``attn.select`` record) for every call the
    calling thread made since its last flush (:func:`routing_detail`,
    :func:`mtp_detail`, :func:`selection_detail`).  The flush fetches them, so it waits for those
    steps: call it where the host waits anyway (the end of a round).
    Disarmed, nothing is kept or fetched."""

    adam = adam_part(lr, b1, b2, eps)

    def loss_fn(lora, base, ids):
        loss, aux, terms = lora_loss_terms(
            lora, base, ids, config, attn_fn=attn_fn
        )
        return loss, (routing_counts(aux), terms, selection_stats(aux))

    def decoder_lora_step(lora, opt, base, ids):
        (loss, (counts, terms, visits)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(lora, base, ids)
        lora, opt = adam(lora, grads, opt)
        return lora, opt, loss, counts, terms, visits

    jitted = jax.jit(decoder_lora_step)
    # thread -> [(t, counts, terms, visits, ids shape, rank)]
    kept = collections.defaultdict(list)

    def step(lora, opt, base, ids):
        out = jitted(lora, opt, base, ids)
        if telemetry.armed() and any(v is not None for v in out[3:]):
            kept[threading.get_ident()].append(
                (time.time(), *out[3:], ids.shape, _expert_rank(lora))
            )
        return out[:4]

    def flush_routing():
        for t, counts, terms, visits, shape, rank in kept.pop(
            threading.get_ident(), ()
        ):
            tokens = shape[0] * shape[1]
            if counts is not None:
                telemetry.emit("moe.counts", t_start=t, detail=routing_detail(
                    counts, config, tokens, rank
                ))
            if terms is not None:
                telemetry.emit("mtp.loss", t_start=t,
                               detail=mtp_detail(terms, tokens))
            if visits is not None:
                telemetry.emit("attn.select", t_start=t,
                               detail=selection_detail(visits, config, shape))

    step.jitted, step.flush_routing = jitted, flush_routing
    return step


def _expert_rank(lora) -> Optional[int]:
    """The rank of the routed experts' adapters; None where they have
    none."""
    for group in lora.get("layers", {}).values():
        for entry in group.get("moe", {}).get("experts", {}).values():
            return entry["a"].shape[-1]
    return None


def routing_detail(counts, config: DecoderConfig, tokens: int,
                   rank: Optional[int] = None) -> dict:
    """The ``moe.counts`` record's detail from a step's counts (fetches
    them).  Per expert layer the rows each held expert's grouped
    products were given, the held share of the step's ``tokens * top_k``
    assignments, and ``dropped``: held assignments that no product was
    given (the layer has no capacity, so 0 unless the chunk loop skipped
    rows).  With the experts' adapter ``rank``, the form of their LoRA
    bypass (``moe.lora_blocks``): ``lora_blocks`` (1: the dense form) of
    ``lora_block_experts`` experts each."""
    import numpy as np

    counts = np.asarray(counts)
    moe_layers = [i for i, s in enumerate(config.stack) if s.ffn == "moe"]
    e = config.experts
    assignments = tokens * e.top_k
    widths = {}
    if e.latent is not None or e.activation != "swiglu" or e.shared_d_ff:
        widths = {"latent": e.latent, "d_ff": e.d_ff,
                  "shared_d_ff": e.shared_width, "activation": e.activation}
    form = {}
    if rank is not None:
        blocks, per_block = moe.lora_blocks(len(e.held), rank)
        form = {"lora_blocks": blocks, "lora_block_experts": per_block}
    return {
        "tokens": int(tokens),
        "top_k": e.top_k,
        "held": list(e.held),
        **widths,
        **form,
        # rows of one chunk of the sorted buffer (moe._routed's loop)
        "chunk_rows": moe._chunk_rows(tokens, config.experts)[0],
        "layers": [
            {
                "layer": i,
                "counts": [int(v) for v in row[:-1]],
                "held_share": float(row[-1]) / assignments,
                "dropped": int(row[-1] - row[:-1].sum()),
            }
            for i, row in zip(moe_layers, counts)
        ],
        "dropped": int((counts[:, -1] - counts[:, :-1].sum(axis=1)).sum()),
    }


def selection_detail(visits, config: DecoderConfig, shape) -> dict:
    """The ``attn.select`` record's detail from a step's
    :func:`selection_stats` (fetches them), per block-sparse layer: the
    mean causal keys a query visited and blocks it selected, beside the
    causal keys dense attention would visit (``(T + 1) / 2``); the
    (query tile, key tile) pairs the kernels walked a K/V head, beside
    the causal pairs, the most a selection can make them walk; and the
    shares of (query, K/V head) rows whose threshold score a left-out
    block shared (``tie_rows``: the block index decided) and with fewer
    than ``topk`` causal blocks (``short_rows``), beside the
    compare-and-count passes the top-k mask makes a row
    (``threshold_passes``)."""
    import numpy as np

    s = config.sparse
    layers = [i for i, spec in enumerate(config.stack)
              if spec.mixer == "sparse"]
    return {
        "batch": int(shape[0]), "tokens": int(shape[1]),
        "block": s.block_size, "topk": s.topk,
        "dense_keys": (shape[1] + 1) / 2,
        "causal_pairs": sparse.causal_pairs(shape[1] // s.tile_for(shape[1])),
        "threshold_passes": sparse.threshold_passes(
            -(-shape[1] // s.block_size)
        ),
        "layers": [
            {"layer": i, **{name: float(v)
                            for name, v in zip(_SELECTION_STATS, row)}}
            for i, row in zip(layers, np.asarray(visits))
        ],
    }


def mtp_detail(terms, tokens: int) -> dict:
    """The ``mtp.loss`` record's detail from a step's loss terms
    (fetches them): the main and the MTP module's loss, the weight of the
    latter and the total the step differentiated."""
    main, mtp = (float(v) for v in terms)
    return {"tokens": int(tokens), "main": main, "mtp": mtp,
            "loss_weight": MTP_LOSS_WEIGHT,
            "total": main + MTP_LOSS_WEIGHT * mtp}
