"""Llama-3-style decoder (RMSNorm, RoPE, GQA, SwiGLU) — the LLM workload.

Covers BASELINE.md config #4 (cross-silo Llama LoRA federated
fine-tune).  TPU-first design decisions:

- **Stacked layer params + ``lax.scan``**: all layers live in one pytree
  with a leading layer dim, the forward scans over it — one compiled
  layer body regardless of depth (fast compiles, natural pipeline
  stages), optionally rematerialized (``remat=True``) to trade FLOPs for
  HBM.
- **Pluggable attention**: dense, pallas flash, ring (sp axis) or
  Ulysses drop in via ``attn_fn`` — long-context sequence parallelism is
  a constructor argument, not a model rewrite.
- **bfloat16 activations** with float32 RMSNorm/softmax/logits.
- **LoRA as a low-rank bypass** (``x@A@B`` added to ``x@W``), never
  materializing ``W + AB`` — see :mod:`rayfed_tpu.models.lora`.

TP/FSDP partition rules shard attention heads and FFN width over ``tp``
and the remaining big dims over ``fsdp``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from rayfed_tpu import telemetry
from rayfed_tpu.models.moe import FFN_UP_NAME, SELECTED_NAME, swiglu
from rayfed_tpu.ops.attention import NEG_INF, dot_product_attention
from rayfed_tpu.ops.flash_attention import RESIDUAL_NAMES
from rayfed_tpu.ops.sparse_attention import SELECTION_NAME

Params = Dict[str, Any]

# What a rematerialized layer body keeps besides its input, here and in
# ``decoder.py``: the experts a routed layer selected (selected again
# from recomputed scores they need not be the same), the flash kernel's
# output and row statistics, which only the kernel can make again, the
# gated FFN's up product (``B*T*F`` elements of the compute dtype a
# dense FFN or shared expert): the second forward makes ``silu(gate) *
# up`` from a recomputed gate and the saved up, one FFN-width product
# less a layer; the residual stream between a layer's two sub-blocks
# (``LAYER_MID_NAME``, ``B*T*D`` elements, named right after the mixer's
# residual add): the second forward starts the FFN half from it, so the
# output projection's product and its adapter's wide one are dead there
# and JAX drops them (what the backward pass needs of that projection,
# its input and the adapter's ``x a``, is still made); and a state-space
# layer's input projection (``SSM_IN_NAME``, ``B*T*proj_dim`` elements,
# named in ``mamba2.apply_mixer``: gate, convolved part and time step
# are slices of it); and a block-sparse attention layer's selection
# (``SELECTION_NAME``, the words and visit lists its kernels read, named
# in ``ops/sparse_attention.py``: a selection made again from
# recomputed scores need not be the one the forward pass used).  Each
# is an array the first forward leaves in HBM
# in the compute dtype anyway; JAX puts a ``reduce_precision`` on a kept
# residual's producer, so its forward consumers read the ROUNDED array
# (what the program says) where XLA's excess precision let a fused one
# read the float32 it was rounded from (the FFN norm's statistic: the
# benchmark's losses move by 1e-7 to 5e-6 relative, either sign; with
# ``xla_allow_excess_precision`` off no bit moves: PERF.md section 6,
# PR 38).  Everything else of the layer's forward is recomputed.  A name
# engages where a layer's code makes the value: one policy for every
# model.
# The gate product (``moe.FFN_GATE_NAME``) is NOT kept: kept in bf16 it
# moved the losses of the cells that adapt the FFN by 4e-4 (PERF.md
# section 6, PR 34).
LAYER_MID_NAME, SSM_IN_NAME = "layer.mid", "ssm.in"
REMAT_SAVED_NAMES = (
    SELECTED_NAME, *RESIDUAL_NAMES, FFN_UP_NAME, LAYER_MID_NAME, SSM_IN_NAME,
    SELECTION_NAME,
)
REMAT_SAVED = jax.checkpoint_policies.save_only_these_names(*REMAT_SAVED_NAMES)


def remat_saved_bytes(tokens: int, dtype, *, hidden: int, ffn_up: int,
                      top_k: int = 0, ssm_in: int = 0,
                      selection: int = 0) -> Dict[str, int]:
    """``{name: bytes ONE checkpointed layer keeps under it}`` for the
    names of ``REMAT_SAVED_NAMES`` that the models' own code gives (the
    flash kernel's two are counted where it is called), from the layer's
    widths: ``hidden`` the stream's, ``ffn_up`` the dense FFN's or the
    shared expert's up product (a gated or a squared-ReLU one alike),
    ``top_k`` an expert layer's selections a token, ``ssm_in`` a
    state-space layer's input projection's and ``selection`` the bytes
    of a block-sparse layer's selection arrays (0: the layer has none; a
    layer with no FFN, ``ffn_up`` 0, keeps neither ``ffn.up`` nor the
    stream between its sub-blocks, since it has one sub-block).  The one
    table behind FR ``remat.saved``, here and in ``decoder.py``: a name
    added to the policy is priced here.  The stream is kept whether or
    not the second forward is spared the output projection by it (a
    norm on that product needs it again)."""
    elem = tokens * jnp.dtype(dtype).itemsize
    sizes = {}
    if ffn_up:
        sizes = {FFN_UP_NAME: elem * ffn_up, LAYER_MID_NAME: elem * hidden}
    if top_k:
        sizes[SELECTED_NAME] = tokens * top_k * 4
    if ssm_in:
        sizes[SSM_IN_NAME] = elem * ssm_in
    if selection:
        sizes[SELECTION_NAME] = selection
    return sizes


# The scope vocabulary of a training step outside its layers (inside a
# layer: ``attn.proj``, ``attn.window`` / ``attn.full``, ``ffn.dense``
# here; ``decoder.py``, ``moe.py`` and ``mamba2.py`` give theirs): what a
# device trace's reader charges an operation to, from the ``op_name`` of
# its instruction in the compiled step's text.  The pass needs no scope:
# JAX writes ``rematted_computation`` into the ``op_name`` of what a
# checkpointed layer's second forward runs and ``transpose(`` into the
# backward pass's.
EMBED_SCOPE, HEAD_LOSS_SCOPE, ADAM_SCOPE = "embed", "head.loss", "optim.adam"


def step_part(scope: str, fn: Callable, **jit_kw) -> Callable:
    """``fn`` as a named part of a jitted step: under
    ``jax.named_scope(scope)`` AND as an inner ``jax.jit`` (``jit_kw``
    its keywords) whose symbol is the scope's name (``optim.adam`` ->
    ``optim_adam``).

    The scope alone is metadata, and the persistent compile cache keys a
    program on its text WITHOUT locations (``jax/_src/cache_key.py``
    strips debug info): a step that differs from an older build's by
    scopes alone loads the older executable, whose text and trace carry
    the older ``op_name``s.  A private function's symbol the key keeps
    and the compiler discards (XLA inlines the call: the compiled step
    has the same instructions), so a step built from these parts never
    hashes to a step built without them."""

    def part(*args, **kw):
        with jax.named_scope(scope):
            return fn(*args, **kw)

    part.__name__ = part.__qualname__ = scope.replace(".", "_")
    return jax.jit(part, **jit_kw)


def _embed_lookup(table, ids, *, dtype, scale=None):
    """Rows ``ids`` of ``table`` in ``dtype``, times a model's embedding
    multiplier where it has one."""
    x = table.astype(dtype)[ids]
    if scale is None:
        return x
    return (x * jnp.asarray(scale, dtype)).astype(dtype)


# One jitted object for every caller: an eager forward finds it compiled.
embed_part = step_part(
    EMBED_SCOPE, _embed_lookup, static_argnames=("dtype", "scale")
)


def checkpoint_layer(body, policy, layers: int):
    """``jax.checkpoint`` of a layer body that ``lax.scan`` runs
    ``layers`` times.  Inside a loop the barrier ``prevent_cse`` puts on
    every residual guards nothing, and it makes a saved residual cost
    more than its bytes (0.3-0.8 GB of a benchmark step's temporaries):
    off.  A scan of ONE layer XLA unrolls, and there the barrier is
    what keeps it from merging the second forward into the first, that
    is from keeping the whole layer (+1.3 GB in the step of the
    benchmark's Kimi cell compiled for the TPU, +0.85 in Trinity's,
    each for its dense layer 0): on."""
    return jax.checkpoint(body, policy=policy, prevent_cse=layers == 1)


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    intermediate_size: int = 14336
    rope_theta: float = 500000.0
    rms_eps: float = 1e-5
    max_seq_len: int = 8192
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    # Storage dtype of the params.  float32 master weights are the
    # default; bfloat16 halves the param+grad HBM footprint (what lets a
    # ~1B-param model + Adam fit a single 16 GB v5e chip) at the cost of
    # rounding away updates below ~0.2% of a weight's magnitude.  Adam's
    # first moment follows this dtype; the second moment is always
    # float32 (see init_adam for why).
    param_dtype: Any = jnp.float32
    # ``jax.checkpoint`` of the scanned layer body.  A layer keeps its
    # input and (``REMAT_SAVED``) the FFN's up product, the residual
    # stream between its two sub-blocks and, where ``attn_fn`` is the
    # flash kernel, the kernel's output and row statistics, so the
    # backward pass runs the layer's q/k/v projections, norms, rotary
    # embedding and the FFN's gate product again, but not the attention
    # kernel, the output projection nor the up product: ``B*T*H*Dh``
    # elements of ``dtype`` + ``B*H*T*4`` bytes a layer for the kernel's
    # (68.2 MB a sequence of 8,192 at 32 x 128 heads in bf16), ``B*T*F``
    # elements for the FFN's (234.9 MB at F = 14,336) and ``B*T*D`` for
    # the stream (67.1 MB at D = 4,096): 370 MB a layer beside its input
    # (11.8 GB at 32 layers), about two fifths of what ``remat=False``
    # would keep.
    remat: bool = False
    # Rematerialization policy for the scanned layer body: None =
    # recompute everything but the above (lowest memory); "dots" = also
    # keep matmul outputs with no batch dims resident
    # (jax.checkpoint_policies.dots_with_no_batch_dims_saveable) — ~5%
    # higher MFU when the activations fit (v5e 1B bench: 0.522 -> 0.566
    # at b=2 seq=2048).
    remat_policy: Optional[str] = None
    # int8 KV cache (per-position-per-head symmetric scales over the
    # head dim): halves the cache's HBM footprint AND the per-token
    # cache traffic of the decode step — the long-context serving lever
    # (at T≈2048 the bf16 cache reads rival the weight reads).  The
    # scales fold into the score/probability tensors, so the cache is
    # read as raw int8 (see make_decode_step).
    kv_quant: bool = False
    # Sliding-window attention (Mistral style): each query sees only its
    # last `sliding_window` keys.  Applied uniformly by the training
    # forward, prefill, AND the decode step's cache mask; with the flash
    # attn_fn the out-of-band kv blocks are skipped in the kernel grid
    # (O(T·W) FLOPs).
    sliding_window: Optional[int] = None

    def __post_init__(self):
        if self.sliding_window is not None and self.sliding_window < 1:
            raise ValueError(
                f"sliding_window must be >= 1, got {self.sliding_window}"
            )
        if self.remat_policy not in (None, "dots"):
            raise ValueError(
                f"unknown remat_policy {self.remat_policy!r} "
                f"(expected None or 'dots')"
            )
        if self.remat_policy is not None and not self.remat:
            raise ValueError(
                "remat_policy is set but remat=False — the policy would "
                "silently never apply; enable remat or drop the policy"
            )

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


def llama3_8b(**kw) -> LlamaConfig:
    return LlamaConfig(**kw)


def llama_tiny(**kw) -> LlamaConfig:
    """Test-scale config (runs on the CPU mesh in seconds)."""
    defaults = dict(
        vocab_size=256,
        hidden_size=64,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        intermediate_size=128,
        max_seq_len=128,
        dtype=jnp.float32,
    )
    defaults.update(kw)
    return LlamaConfig(**defaults)


def init_llama(key: jax.Array, config: LlamaConfig) -> Params:
    d = config.hidden_size
    dh = config.head_dim
    h, kv = config.num_heads, config.num_kv_heads
    f = config.intermediate_size
    L = config.num_layers
    k_embed, k_layers, k_head = jax.random.split(key, 3)

    pdt = config.param_dtype

    def dense(key, *shape, fan_in):
        return (jax.random.normal(key, shape) * fan_in**-0.5).astype(pdt)

    lk = jax.random.split(k_layers, 7)
    params: Params = {
        "embed": (
            jax.random.normal(k_embed, (config.vocab_size, d)) * 0.02 * d**0.5
        ).astype(pdt),
        "layers": {
            "attn_norm": jnp.ones((L, d), pdt),
            "wq": dense(lk[0], L, d, h * dh, fan_in=d),
            "wk": dense(lk[1], L, d, kv * dh, fan_in=d),
            "wv": dense(lk[2], L, d, kv * dh, fan_in=d),
            "wo": dense(lk[3], L, h * dh, d, fan_in=h * dh),
            "mlp_norm": jnp.ones((L, d), pdt),
            "w_gate": dense(lk[4], L, d, f, fan_in=d),
            "w_up": dense(lk[5], L, d, f, fan_in=d),
            "w_down": dense(lk[6], L, f, d, fan_in=f),
        },
        "final_norm": jnp.ones((d,), pdt),
    }
    if not config.tie_embeddings:
        params["lm_head"] = dense(k_head, d, config.vocab_size, fan_in=d)
    return params


_QUANT_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def quantize_llama_base(params: Params) -> Params:
    """int8-quantize the frozen base for a LoRA fine-tune.

    The seven stacked [L, din, dout] matmul weights get per-(layer,
    output-channel) scales; ``lm_head`` a per-column scale; embeddings
    and norms stay in their float dtype (gathered/elementwise — no MXU
    matmul to fuse a dequant into).  Halves the bf16 footprint again:
    Llama-3-8B base ≈ 8 GB, fitting a 16 GB v5e chip with adapters +
    Adam moments to spare (BASELINE.json config #4 at literal scale).
    Use with :func:`make_lora_train_step` only — the base must stay
    frozen (int8 leaves carry no gradient).
    """
    from rayfed_tpu.models.quant import quantize_int8

    out = dict(params)
    out["layers"] = {
        k: (
            quantize_int8(v, channel_axis=-1, batch_axes=(0,))
            if k in _QUANT_LEAVES
            else v
        )
        for k, v in params["layers"].items()
    }
    if "lm_head" in params:
        out["lm_head"] = quantize_int8(params["lm_head"], channel_axis=-1)
    return out


def init_llama_int8(key: jax.Array, config: LlamaConfig) -> Params:
    """Random int8-quantized base, built WITHOUT a full-precision pass.

    Each matmul weight is generated directly as int8 (uniform in
    [-127, 127]) with a fan-in-scaled per-channel dequant scale, so peak
    memory during init is the int8 tree itself — at 8B the bf16
    intermediate that ``init_llama`` + :func:`quantize_llama_base` would
    build (~16 GB) never exists.  For benches and scaffolding; real runs
    load quantized checkpoints.
    """
    from rayfed_tpu.models.quant import QTensor

    d = config.hidden_size
    dh = config.head_dim
    h, kv = config.num_heads, config.num_kv_heads
    f = config.intermediate_size
    L = config.num_layers
    pdt = config.param_dtype
    k_embed, k_layers, k_head = jax.random.split(key, 3)

    def qdense(key, *shape, fan_in):
        q = jax.random.randint(key, shape, -127, 128, dtype=jnp.int8)
        # E[q^2] ≈ 127^2/3 ⇒ scale for unit-ish activations: 1/(73·√fan_in).
        scale_shape = (shape[0], *([1] * (len(shape) - 2)), shape[-1])
        scale = jnp.full(scale_shape, (fan_in**-0.5) / 73.0, jnp.float32)
        return QTensor(q=q, scale=scale)

    lk = jax.random.split(k_layers, 7)
    params: Params = {
        "embed": (
            jax.random.normal(k_embed, (config.vocab_size, d), pdt) * 0.02 * d**0.5
        ),
        "layers": {
            "attn_norm": jnp.ones((L, d), pdt),
            "wq": qdense(lk[0], L, d, h * dh, fan_in=d),
            "wk": qdense(lk[1], L, d, kv * dh, fan_in=d),
            "wv": qdense(lk[2], L, d, kv * dh, fan_in=d),
            "wo": qdense(lk[3], L, h * dh, d, fan_in=h * dh),
            "mlp_norm": jnp.ones((L, d), pdt),
            "w_gate": qdense(lk[4], L, d, f, fan_in=d),
            "w_up": qdense(lk[5], L, d, f, fan_in=d),
            "w_down": qdense(lk[6], L, f, d, fan_in=f),
        },
        "final_norm": jnp.ones((d,), pdt),
    }
    if not config.tie_embeddings:
        head = jax.random.randint(
            k_head, (d, config.vocab_size), -127, 128, dtype=jnp.int8
        )
        from rayfed_tpu.models.quant import QTensor as _QT

        params["lm_head"] = _QT(
            q=head,
            scale=jnp.full((1, config.vocab_size), (d**-0.5) / 73.0, jnp.float32),
        )
    return params


def _rms_norm(x, scale, eps):
    xf = x.astype(jnp.float32)
    norm = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (norm * scale.astype(jnp.float32)).astype(x.dtype)


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """A config's ``rope_scaling`` of type ``yarn`` (transformers
    ``modeling_rope_utils.py::_compute_yarn_parameters``): frequencies
    that turn fewer than ``beta_slow`` times over the
    ``original_max_position`` trained positions are divided by
    ``factor`` (interpolated), those that turn more than ``beta_fast``
    times are kept, and the ones between are blended linearly in the
    frequency's index."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    def magnitude(self, mscale: float) -> float:
        """``m(s) = 0.1 s ln(factor) + 1`` (1 where nothing is scaled)."""
        if self.factor <= 1:
            return 1.0
        return 0.1 * mscale * math.log(self.factor) + 1.0

    def table_scale(self) -> float:
        """What the cos and sin tables are multiplied by."""
        if self.mscale and self.mscale_all_dim:
            return self.magnitude(self.mscale) / self.magnitude(
                self.mscale_all_dim
            )
        return self.magnitude(1.0)

    def softmax_scale(self) -> float:
        """What an attention whose scores the scaled positions enter
        multiplies its ``width ** -0.5`` by (``DeepseekV3Attention``):
        ``m(mscale_all_dim) ** 2``."""
        if not self.mscale_all_dim:
            return 1.0
        return self.magnitude(self.mscale_all_dim) ** 2

    def blend(self, head_dim: int, theta: float):
        """``(low, high)``: the frequency indices between which the
        blend runs, clipped to ``[0, head_dim - 1]``."""
        def turns_at(n):  # the index whose frequency turns n times
            return head_dim * math.log(
                self.original_max_position / (n * 2 * math.pi)
            ) / (2 * math.log(theta))

        low = max(math.floor(turns_at(self.beta_fast)), 0)
        high = min(math.ceil(turns_at(self.beta_slow)), head_dim - 1)
        return low, high


def rope_frequencies(head_dim: int, theta: float,
                     scaling: Optional[YarnScaling] = None):
    """The ``head_dim / 2`` rotary frequencies, float32."""
    freqs = 1.0 / theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    if scaling is None:
        return freqs
    low, high = scaling.blend(head_dim, theta)
    ramp = jnp.clip(
        (jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
        / (high - low if high != low else 0.001), 0.0, 1.0,
    )
    return freqs / scaling.factor * ramp + freqs * (1.0 - ramp)


def rope_tables(positions: jax.Array, head_dim: int, theta: float,
                scaling: Optional[YarnScaling] = None):
    """cos/sin tables [T, head_dim/2] for the given absolute positions
    (``scaling``: YaRN's blended frequencies and table scale)."""
    freqs = rope_frequencies(head_dim, theta, scaling)
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    by = 1.0 if scaling is None else scaling.table_scale()
    if by == 1.0:
        return jnp.cos(angles), jnp.sin(angles)
    return jnp.cos(angles) * by, jnp.sin(angles) * by


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate pairs (x[..., ::2], x[..., 1::2]); x: [B, T, H, Dh]."""
    x1 = x[..., 0::2]
    x2 = x[..., 1::2]
    c = cos[None, :, None, :].astype(x.dtype)
    s = sin[None, :, None, :].astype(x.dtype)
    r1 = x1 * c - x2 * s
    r2 = x2 * c + x1 * s
    return jnp.stack([r1, r2], axis=-1).reshape(x.shape)


def _linear(x, w, lora_entry, dtype):
    """x @ w with an optional LoRA low-rank bypass (x@A)@B · scale.

    ``w`` may be an int8 :class:`~rayfed_tpu.models.quant.QTensor` (frozen
    base in a LoRA fine-tune); quant.matmul keeps the weight-side dequant
    a pure fusable convert (scale applied to the output) so decode reads
    int8 bytes from HBM, not a materialized bf16 copy."""
    from rayfed_tpu.models.quant import matmul

    out = matmul(x, w, dtype)
    if lora_entry is not None:
        a = lora_entry["a"].astype(dtype)
        b = lora_entry["b"].astype(dtype)
        scale = jax.lax.stop_gradient(lora_entry["scale"]).astype(dtype)
        out = out + (x @ a) @ b * scale
    return out


def _no_lora(name):
    return None


def _qkv_proj(y, lp, config, b, t, lget=_no_lora):
    """Project + reshape + RoPE-ready q/k/v — shared by the training
    forward and the KV-cache decode step (keep their numerics in sync)."""
    h, kv, dh = config.num_heads, config.num_kv_heads, config.head_dim
    dtype = config.dtype
    q = _linear(y, lp["wq"], lget("wq"), dtype).reshape(b, t, h, dh)
    k = _linear(y, lp["wk"], lget("wk"), dtype).reshape(b, t, kv, dh)
    v = _linear(y, lp["wv"], lget("wv"), dtype).reshape(b, t, kv, dh)
    return q, k, v


def _attn_out(x, attn, lp, config, b, t, lget=_no_lora):
    flat = attn.reshape(b, t, config.num_heads * config.head_dim)
    return x + _linear(flat, lp["wo"], lget("wo"), config.dtype)


def _mlp_block(x, lp, config, lget=_no_lora):
    """RMSNorm + SwiGLU MLP residual — shared by training and decode."""
    y = _rms_norm(x, lp["mlp_norm"], config.rms_eps)
    return x + swiglu(y, lp, lget, config.dtype)


def _layer_fwd(x, lp, config, cos, sin, attn_fn, b, t, lget=_no_lora,
               emit_kv=False):
    """One decoder layer (norm→qkv→RoPE→GQA attn→out→MLP) — the single
    implementation behind the training forward AND prefill, so their
    numerics cannot drift.  GQA: every ``attn_fn`` takes the
    ``num_kv_heads`` K/V as they are (the flash kernel reads them by KV
    head, dense attention contracts over the group, ring and Ulysses
    repeat inside their wrapper).  With ``emit_kv`` also returns that
    k/v (for KV-cache assembly)."""
    with jax.named_scope("attn.proj"):
        y = _rms_norm(x, lp["attn_norm"], config.rms_eps)
        q, k, v = _qkv_proj(y, lp, config, b, t, lget)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    if config.sliding_window is not None:
        # Both dense and flash attn_fns accept window=; an attn_fn that
        # cannot honor it (ring/Ulysses wrappers) fails loudly here
        # rather than silently attending outside the band.
        with jax.named_scope("attn.window"):
            attn = attn_fn(q, k, v, causal=True, window=config.sliding_window)
    else:
        with jax.named_scope("attn.full"):
            attn = attn_fn(q, k, v, causal=True)
    with jax.named_scope("attn.proj"):
        x = _attn_out(x, attn, lp, config, b, t, lget)
        x = checkpoint_name(x, LAYER_MID_NAME)
    with jax.named_scope("ffn.dense"):
        x = _mlp_block(x, lp, config, lget)
    return (x, (k, v)) if emit_kv else (x, None)


def _head_matrix(params, config):
    """``(head [D, V] in the compute dtype, out_scale or None)``: the
    vocabulary projection's operand, and what multiplies its OUTPUT."""
    from rayfed_tpu.models.quant import split_output_scale

    head = params.get("lm_head")
    if head is None:
        return params["embed"].astype(config.dtype).T, None
    # Output-side scale keeps the weight feed a pure int8->bf16
    # convert (see quant.split_output_scale) — the lm_head is the
    # single largest weight read of a decode step.
    return split_output_scale(head, config.dtype)


def _lm_head(x, params, config):
    """Final norm + vocabulary projection ([..., D] → [..., V] f32).

    bf16 MXU operands, f32 accumulation — a pure-f32 lm_head matmul runs
    at a fraction of bf16 throughput and the f32 accumulator already
    carries the precision the loss needs.
    """
    x = _rms_norm(x, params["final_norm"], config.rms_eps)
    head, out_scale = _head_matrix(params, config)
    logits = jax.lax.dot_general(
        x.astype(config.dtype),
        head,
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if out_scale is not None:
        logits = logits * out_scale.astype(logits.dtype)
    return logits


def apply_llama(
    params: Params,
    input_ids: jax.Array,
    config: LlamaConfig,
    *,
    lora: Optional[Params] = None,
    attn_fn: Callable = dot_product_attention,
    positions: Optional[jax.Array] = None,
) -> jax.Array:
    """Forward: [B, T] ids → [B, T, V] float32 logits (causal LM)."""
    x = _hidden_states(params, input_ids, config, lora, attn_fn, positions)
    return _lm_head(x, params, config)


def _hidden_states(params, input_ids, config, lora, attn_fn, positions=None):
    """The residual stream after the last layer, [B, T, D] (before the
    final norm)."""
    b, t = input_ids.shape
    dtype = config.dtype
    h, kv, dh = config.num_heads, config.num_kv_heads, config.head_dim

    x = embed_part(params["embed"], input_ids, dtype=jnp.dtype(dtype))
    if positions is None:
        positions = jnp.arange(t)
    with jax.named_scope("attn.proj"):
        cos, sin = rope_tables(positions, dh, config.rope_theta)

    lora_layers = (lora or {}).get("layers")
    # Scan xs need a leading layer dim on every leaf — hoist the scalar
    # LoRA scales out of the scanned tree into the closure.
    lora_scales = {}
    if lora_layers is not None:
        lora_scales = {k: v["scale"] for k, v in lora_layers.items()}
        lora_layers = {
            k: {"a": v["a"], "b": v["b"]} for k, v in lora_layers.items()
        }

    def layer_body(x, scanned):
        lp = scanned["w"]
        ll = scanned.get("lora")

        def lget(name):
            if ll is None or name not in ll:
                return None
            return {**ll[name], "scale": lora_scales[name]}

        return _layer_fwd(x, lp, config, cos, sin, attn_fn, b, t, lget)

    if config.remat:
        # Values are validated in LlamaConfig.__post_init__; the explicit
        # dispatch stays exhaustive so a future policy added to the
        # whitelist cannot silently fall through to the wrong one.
        if config.remat_policy is None:
            layer_body = checkpoint_layer(
                layer_body, REMAT_SAVED, config.num_layers
            )
        elif config.remat_policy == "dots":
            layer_body = checkpoint_layer(
                layer_body,
                jax.checkpoint_policies.save_from_both_policies(
                    jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                    REMAT_SAVED,
                ),
                config.num_layers,
            )
        else:  # pragma: no cover — unreachable past __post_init__
            raise AssertionError(config.remat_policy)

    scanned = {"w": params["layers"]}
    if lora_layers is not None:
        scanned["lora"] = lora_layers
    with jax.named_scope(f"layers0-{config.num_layers - 1}"):
        x, _ = jax.lax.scan(layer_body, x, scanned)
    return x


# ---------------------------------------------------------------------------
# KV-cache decoding (autoregressive inference)
# ---------------------------------------------------------------------------


def _quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric int8 over the trailing (head) dim: [..., Dh] →
    (int8 [..., Dh], f32 scale [..., 1]).  Zero vectors quantize to
    zeros (scale floor), so fresh cache slots stay exact."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def init_kv_cache(config: LlamaConfig, batch: int, max_len: int) -> Params:
    """Static-shape KV cache: ``k``/``v`` are [L, B, max_len, KV, Dh].

    Static shapes keep the decode step a single compiled XLA program —
    position advances by ``dynamic_update_slice`` writes plus a length
    mask, never a shape change.

    With ``config.kv_quant`` the k/v planes are int8 and per-(position,
    head) f32 scales ride alongside as ``k_scale``/``v_scale``
    [L, B, max_len, KV, 1] — 0.53× the bf16 cache bytes.
    """
    kvh, dh, L = config.num_kv_heads, config.head_dim, config.num_layers
    shape = (L, batch, max_len, kvh, dh)
    if config.kv_quant:
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(shape[:-1] + (1,), jnp.float32),
            "v_scale": jnp.zeros(shape[:-1] + (1,), jnp.float32),
        }
    return {
        "k": jnp.zeros(shape, config.dtype),
        "v": jnp.zeros(shape, config.dtype),
    }


def init_rolling_kv_cache(config: LlamaConfig, batch: int) -> Params:
    """Ring-buffer cache of exactly ``sliding_window`` slots — decode
    memory stays O(W) for unbounded generation (pair with
    ``make_decode_step(config, rolling=True)``)."""
    if config.sliding_window is None:
        raise ValueError("a rolling cache requires config.sliding_window")
    return init_kv_cache(config, batch, config.sliding_window)


def roll_kv_cache(cache: Params, config: LlamaConfig, t0: int) -> Params:
    """Re-layout a (prefilled) linear cache into the rolling ring buffer.

    ``t0``: tokens already in the cache (prefill length).  Slot ``i`` of
    the ring receives the newest cached position congruent to ``i`` mod
    W; slots whose position would be negative (``t0 < W``) hold garbage
    that the rolling step's validity arithmetic masks out.
    """
    w = config.sliding_window
    if w is None:
        raise ValueError("roll_kv_cache requires config.sliding_window")
    max_len = cache["k"].shape[2]
    last = t0 - 1
    slots = jnp.arange(w)
    src = last - jnp.mod(last - slots, w)  # abs position for slot i
    src_idx = jnp.clip(src, 0, max_len - 1)
    return {
        name: jnp.take(plane, src_idx, axis=2)
        for name, plane in cache.items()
    }


@functools.lru_cache(maxsize=None)
def make_decode_step(config: LlamaConfig, rolling: bool = False):
    """One-token autoregressive step as a single jitted program.

    Returns ``step(params, cache, token_ids, pos) -> (cache, logits)``:
    ``token_ids`` is [B] (this position's token per sequence), ``pos`` a
    traced scalar position; ``logits`` is [B, V] float32 for the NEXT
    token.  The cache (donated) is updated in place in HBM.

    Numerics match :func:`apply_llama` at the same position (the layer
    math is shared via ``_qkv_proj``/``_attn_out``/``_mlp_block``): same
    RoPE tables, f32 softmax over the masked cache, bf16 MXU matmuls
    with f32 accumulation for the lm_head.  Cached per config (frozen
    dataclass) so repeat callers reuse the compiled program.

    ``rolling`` (requires ``config.sliding_window``): the cache is a
    ring buffer of exactly ``W`` slots (:func:`init_rolling_kv_cache`)
    — token ``pos`` writes slot ``pos % W``, and slot validity falls out
    of the ring arithmetic (a slot is live iff its absolute position is
    ≥ 0; the band and causality are automatic because every resident
    position lies in ``(pos − W, pos]``).  Memory stays O(W) for
    unbounded generation; k/v carry RoPE at their absolute positions,
    so scores need no relocation when slots are overwritten.
    """
    if rolling and config.sliding_window is None:
        raise ValueError("rolling=True requires config.sliding_window")
    h, kvh, dh = config.num_heads, config.num_kv_heads, config.head_dim
    dtype = config.dtype

    def step(params, cache, token_ids, pos):
        pos = jnp.asarray(pos)
        b = token_ids.shape[0]
        max_len = cache["k"].shape[2]
        x = params["embed"].astype(dtype)[token_ids][:, None, :]  # [B,1,D]
        cos, sin = rope_tables(pos[None], dh, config.rope_theta)
        positions = jnp.arange(max_len)
        if rolling:
            # The ring modulus IS the window; a linear cache passed here
            # by mistake (skipping roll_kv_cache) would silently widen
            # the attention window — reject it at trace time.
            if max_len != config.sliding_window:
                raise ValueError(
                    f"rolling decode needs a {config.sliding_window}-slot "
                    f"ring cache (init_rolling_kv_cache/roll_kv_cache), "
                    f"got {max_len} slots"
                )
            # Slot i holds absolute position pos − ((pos − i) mod W);
            # live iff that position is ≥ 0.
            write_pos = jnp.mod(pos, max_len)
            abs_pos = pos - jnp.mod(pos - positions, max_len)
            valid = abs_pos >= 0
        else:
            # Valid-length mask over the static cache: positions <= pos
            # (and, under sliding-window attention, within the band).
            write_pos = pos
            valid = positions <= pos  # [T]
            if config.sliding_window is not None:
                valid = valid & (positions > pos - config.sliding_window)

        def layer_body(x, scanned):
            lp = scanned["w"]
            k_cache = scanned["k"]  # [B, T, KV, Dh]
            v_cache = scanned["v"]

            y = _rms_norm(x, lp["attn_norm"], config.rms_eps)
            q, k, v = _qkv_proj(y, lp, config, b, 1)
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
            out_cache = {}
            if config.kv_quant:
                k_q, k_s = _quantize_kv(k)
                v_q, v_s = _quantize_kv(v)
                k_cache = jax.lax.dynamic_update_slice(
                    k_cache, k_q, (0, write_pos, 0, 0)
                )
                v_cache = jax.lax.dynamic_update_slice(
                    v_cache, v_q, (0, write_pos, 0, 0)
                )
                out_cache["k_scale"] = jax.lax.dynamic_update_slice(
                    scanned["k_scale"], k_s, (0, write_pos, 0, 0)
                )
                out_cache["v_scale"] = jax.lax.dynamic_update_slice(
                    scanned["v_scale"], v_s, (0, write_pos, 0, 0)
                )
            else:
                k_cache = jax.lax.dynamic_update_slice(
                    k_cache, k.astype(k_cache.dtype), (0, write_pos, 0, 0)
                )
                v_cache = jax.lax.dynamic_update_slice(
                    v_cache, v.astype(v_cache.dtype), (0, write_pos, 0, 0)
                )
            # GQA: group query heads over the shared kv head (g = H/KV).
            # Native-dtype (bf16) MXU operands with f32 accumulation —
            # casting the whole static cache to f32 would materialize
            # multi-MB copies per layer in the per-token hot loop.
            g = h // kvh
            qs = (q.reshape(b, h, dh) * dh**-0.5).astype(dtype)
            qs = qs.reshape(b, kvh, g, dh)
            s = jnp.einsum(
                "bngd,btnd->bngt", qs, k_cache.astype(dtype),
                preferred_element_type=jnp.float32,
            )
            if config.kv_quant:
                # The per-(position, head) k scale is constant over the
                # contracted head dim, so it factors out of the dot and
                # lands on the small [B, KV, g, T] score tensor — the
                # int8 cache plane is read raw, never dequantized in HBM.
                k_s_t = out_cache["k_scale"][..., 0].transpose(0, 2, 1)
                s = s * k_s_t[:, :, None, :]
            s = jnp.where(valid[None, None, None, :], s, NEG_INF)
            p = jax.nn.softmax(s, axis=-1)
            if config.kv_quant:
                # Same trick on the value side: fold the v scale into
                # the probabilities before the weighted sum.
                v_s_t = out_cache["v_scale"][..., 0].transpose(0, 2, 1)
                p = p * v_s_t[:, :, None, :]
            attn = jnp.einsum(
                "bngt,btnd->bngd", p.astype(dtype), v_cache.astype(dtype),
                preferred_element_type=jnp.float32,
            )  # [B, KV, g, Dh]
            attn = attn.reshape(b, 1, h, dh).astype(dtype)
            x = _attn_out(x, attn, lp, config, b, 1)
            x = _mlp_block(x, lp, config)
            out_cache["k"] = k_cache
            out_cache["v"] = v_cache
            return x, out_cache

        scanned = {"w": params["layers"], "k": cache["k"], "v": cache["v"]}
        if config.kv_quant:
            scanned["k_scale"] = cache["k_scale"]
            scanned["v_scale"] = cache["v_scale"]
        x, new_cache = jax.lax.scan(layer_body, x, scanned)

        return new_cache, _lm_head(x[:, 0, :], params, config)

    return jax.jit(step, donate_argnums=(1,))


def prefill(
    params: Params,
    config: LlamaConfig,
    prompt_ids: jax.Array,
    max_len: int,
    *,
    attn_fn: Callable = dot_product_attention,
) -> Tuple[Params, jax.Array]:
    """Process the whole prompt in ONE causal pass and return
    ``(cache, last_logits)`` ready for :func:`make_decode_step`.

    Same layer math as :func:`apply_llama` (shared helpers), but the
    scan also emits each layer's k/v, zero-padded into the static
    [L, B, max_len, KV, Dh] cache layout.  O(T) matmul width instead of
    T sequential single-token steps.
    """
    b, t0 = prompt_ids.shape
    if t0 > max_len:
        raise ValueError(f"prompt length {t0} exceeds cache max_len {max_len}")
    dtype = config.dtype
    h, kv, dh = config.num_heads, config.num_kv_heads, config.head_dim

    x = params["embed"].astype(dtype)[prompt_ids]
    cos, sin = rope_tables(jnp.arange(t0), dh, config.rope_theta)

    def layer_body(x, lp):
        x, (k_out, v_out) = _layer_fwd(
            x, lp, config, cos, sin, attn_fn, b, t0, emit_kv=True
        )
        pad = [(0, 0), (0, max_len - t0), (0, 0), (0, 0)]
        if config.kv_quant:
            # Same quantizer as the decode step, position by position —
            # a prefilled cache matches sequential decode's up to the
            # matmul-shape-dependent last-ulp of the projections
            # (dequantized agreement tested).
            k_q, k_s = _quantize_kv(k_out)
            v_q, v_s = _quantize_kv(v_out)
            return x, {
                "k": jnp.pad(k_q, pad),
                "v": jnp.pad(v_q, pad),
                "k_scale": jnp.pad(k_s, pad),
                "v_scale": jnp.pad(v_s, pad),
            }
        return x, {"k": jnp.pad(k_out, pad), "v": jnp.pad(v_out, pad)}

    x, cache = jax.lax.scan(layer_body, x, params["layers"])

    return cache, _lm_head(x[:, -1, :], params, config)


def generate(
    params: Params,
    config: LlamaConfig,
    prompt_ids: jax.Array,
    max_new_tokens: int,
    *,
    attn_fn: Callable = dot_product_attention,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    key: Optional[jax.Array] = None,
) -> jax.Array:
    """Autoregressive decoding: [B, T0] prompt → [B, T0+max_new_tokens].

    One batched causal pass over the prompt (:func:`prefill`, pass
    ``attn_fn=flash_attention`` for long prompts — dense attention
    materializes the [B,H,T,T] score tensor), then one ``lax.scan`` of
    single-token steps through the KV cache.

    ``temperature=0`` (default) is greedy argmax.  With a positive
    temperature, samples from softmax(logits/temperature), optionally
    truncated to the ``top_k`` most likely tokens; ``key`` is then
    required.
    """
    if temperature < 0.0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    if temperature > 0.0 and key is None:
        raise ValueError("sampling (temperature > 0) requires key=")
    if temperature == 0.0 and (key is not None or top_k is not None):
        # The mirror mistake of the check above: sampling args that would
        # be silently ignored under greedy decoding.
        raise ValueError(
            "key/top_k are sampling arguments — pass temperature > 0 "
            "(or drop them for greedy decoding)"
        )
    if top_k is not None and not 0 < top_k <= config.vocab_size:
        raise ValueError(
            f"top_k must be in [1, vocab_size={config.vocab_size}], got {top_k}"
        )
    b, t0 = prompt_ids.shape
    max_len = t0 + max_new_tokens
    cache, logits = prefill(params, config, prompt_ids, max_len, attn_fn=attn_fn)
    step = make_decode_step(config)
    keys = (
        jax.random.split(key, max_new_tokens)
        if temperature > 0.0
        else jnp.zeros((max_new_tokens, 2), jnp.uint32)
    )

    def pick(logits, k):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1)
        scaled = logits / temperature
        if top_k is not None:
            # Partial top-k, not a full vocab sort — this runs inside
            # the per-token decode loop.
            kth = jax.lax.top_k(scaled, top_k)[0][:, -1][:, None]
            scaled = jnp.where(scaled >= kth, scaled, -jnp.inf)
        return jax.random.categorical(k, scaled, axis=-1)

    def gen_body(carry, inputs):
        i, k = inputs
        cache, logits = carry
        token = pick(logits, k).astype(prompt_ids.dtype)
        cache, logits = step(params, cache, token, t0 + i)
        return (cache, logits), token

    (_, logits), tokens = jax.lax.scan(
        gen_body, (cache, logits), (jnp.arange(max_new_tokens), keys)
    )
    return jnp.concatenate([prompt_ids, tokens.T], axis=1)


def greedy_generate(
    params: Params,
    config: LlamaConfig,
    prompt_ids: jax.Array,
    max_new_tokens: int,
    *,
    attn_fn: Callable = dot_product_attention,
) -> jax.Array:
    """Greedy decoding (temperature-0 :func:`generate`)."""
    return generate(
        params, config, prompt_ids, max_new_tokens, attn_fn=attn_fn
    )


def lm_loss(logits: jax.Array, targets: jax.Array, mask=None) -> jax.Array:
    """Next-token cross entropy; ``targets``[i] is the label for pos i."""
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


# The float32 logits one chunk of the fused head-and-loss may hold.
HEAD_CHUNK_BYTES = 128 << 20


def head_chunk_rows(rows: int, vocab: int) -> int:
    """Rows a chunk of :func:`frozen_head_loss`: the largest power of two
    whose float32 logits fit ``HEAD_CHUNK_BYTES`` (1,024 at a vocabulary
    of 32,000), and no more than there are."""
    fit = max(HEAD_CHUNK_BYTES // (4 * vocab), 1)
    return min(1 << (fit.bit_length() - 1), rows)


def _head_loss_chunks(head_rows, xs, head, out_scale, targets, weights,
                      with_dx):
    """Scan the chunks: ``sum(weights * nll)``, and with ``with_dx`` its
    gradient by ``xs``, chunk by chunk.  ``xs`` [n, rows, D], ``targets``
    and ``weights`` [n, rows]; ``head`` [D, V], or with ``head_rows``
    [V, D] (a tied embedding as it lies)."""
    wide, tall = (1, 0) if head_rows else (0, 1)

    def body(total, chunk):
        x, target, weight = chunk
        logits = jax.lax.dot_general(
            x, head, (((1,), (wide,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if out_scale is not None:
            logits = logits * out_scale.astype(jnp.float32)
        # lm_loss's arithmetic, row by row: log_softmax, then the target's.
        shifted = logits - jnp.max(logits, axis=-1, keepdims=True)
        lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
        columns = jax.lax.broadcasted_iota(jnp.int32, shifted.shape, 1)
        hit = columns == target[:, None]
        nll = lse - jnp.sum(jnp.where(hit, shifted, 0.0), axis=-1)
        total = total + jnp.sum(nll * weight)
        if not with_dx:
            return total, None
        # d/d logits = (softmax - onehot) * weight, rounded to the head's
        # type as the transposed product of `lm_loss(_lm_head(x))` rounds
        # it on the chip (its compiled step: bf16 x bf16, bf16 out).
        d_logits = (jnp.exp(shifted - lse[:, None]) - hit) * weight[:, None]
        if out_scale is not None:
            d_logits = d_logits * out_scale.astype(jnp.float32)
        dx = jax.lax.dot_general(
            d_logits.astype(head.dtype), head, (((1,), (tall,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return total, dx.astype(x.dtype)

    return jax.lax.scan(
        body, jnp.zeros((), jnp.float32), (xs, targets, weights)
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _head_loss(head_rows, xs, head, out_scale, targets, weights):
    return _head_loss_chunks(
        head_rows, xs, head, out_scale, targets, weights, False
    )[0]


def _head_loss_fwd(head_rows, xs, head, out_scale, targets, weights):
    return _head_loss_chunks(
        head_rows, xs, head, out_scale, targets, weights, True
    )


def _head_loss_bwd(head_rows, dxs, g):
    # The head is frozen: no cotangent but the hidden states'.  The rule
    # is traced outside the caller's scope: name it again.
    with jax.named_scope(HEAD_LOSS_SCOPE):
        return g.astype(dxs.dtype) * dxs, None, None, None, None


_head_loss.defvjp(_head_loss_fwd, _head_loss_bwd)


def frozen_head_loss(x, head, ids, out_scale=None, *,
                     head_rows: bool = False, shift: int = 1) -> jax.Array:
    """``lm_loss(logits[:, :-1], ids[:, 1:])`` of the final-normed hidden
    states ``x`` [B, T, D] through a FROZEN head [D, V] (``out_scale``
    [V] or a scalar on its output: a quantized head's, a model's logit
    multiplier), with no ``[B, T, V]`` array.  ``head_rows``: the head
    is [V, D], an embedding the model ties its head to, read as it lies.
    ``shift``: position ``i`` predicts ``ids[:, i + shift]`` (2: a
    multi-token-prediction module's next-but-one token), the last
    ``shift`` positions have no target.

    A ``lax.scan`` over chunks of :func:`head_chunk_rows` rows of the
    flattened ``[B*T, D]``: per chunk the logits (``x``'s and the head's
    type, float32 accumulation), ``lm_loss``'s arithmetic on them, and
    in the forward pass of a gradient already ``d loss / d x``, so that
    the only residual is ``[B*T, D]`` of ``x``'s type, the backward rule
    scales it, and the head product runs twice a step (logits, input
    gradient), not a third time.  Per-row results are ``lm_loss``'s; the
    mean sums in another order.  The head takes NO gradient: a step that
    trains it stays on :func:`lm_loss`.
    """
    b, t, _ = x.shape
    rows = b * t
    chunk = head_chunk_rows(rows, head.shape[0 if head_rows else 1])
    n = -(-rows // chunk)

    def chunks(a):
        a = a.reshape(rows, *a.shape[2:])
        a = jnp.pad(a, [(0, n * chunk - rows)] + [(0, 0)] * (a.ndim - 1))
        return a.reshape(n, chunk, *a.shape[1:])

    with jax.named_scope(HEAD_LOSS_SCOPE):
        # The last `shift` positions of each sequence have no target:
        # weight 0, as the rows that pad the last chunk.
        targets = jnp.roll(ids, -shift, axis=1)
        weights = jnp.broadcast_to(
            (jnp.arange(t) < t - shift)
            / jnp.float32(max(b * (t - shift), 1)), (b, t)
        )
        return _head_loss(
            head_rows, chunks(x), head, out_scale, chunks(targets),
            chunks(weights),
        )


def lora_loss(lora, base_params, ids, config: LlamaConfig, *,
              attn_fn: Callable = dot_product_attention) -> jax.Array:
    """Next-token loss of ``ids`` [B, T] with adapters ``lora`` on the
    frozen ``base_params``: what the LoRA steps differentiate.  The head
    is frozen too, so head and loss are fused (:func:`frozen_head_loss`).
    """
    c = config
    x = _hidden_states(base_params, ids, c, lora, attn_fn)
    kept = {}
    if c.remat:
        kept[f"layers0-{c.num_layers - 1}"] = (c.num_layers, remat_saved_bytes(
            ids.size, c.dtype, hidden=c.hidden_size, ffn_up=c.intermediate_size
        ))
    emit_remat_saved(kept, ids.size, c.vocab_size)
    with jax.named_scope(HEAD_LOSS_SCOPE):
        x = _rms_norm(x, base_params["final_norm"], c.rms_eps)
        head, out_scale = _head_matrix(base_params, c)
    return frozen_head_loss(x.astype(c.dtype), head, ids, out_scale)


def emit_remat_saved(groups, rows: int, vocab: int) -> None:
    """While the flight recorder is armed, the ``remat.saved`` record of
    a LoRA step being traced: what its checkpointed layers keep and how
    its head-and-loss is chunked, from static shapes.  ``groups``:
    ``{scanned group: (layers, {name: bytes a layer keeps under it})}``
    for the names the model's own code gives.
    """
    if not telemetry.armed():
        return
    chunk = head_chunk_rows(rows, vocab)
    telemetry.emit(
        "remat.saved",
        detail={
            # the policy's names; the flash kernel's two are tagged in
            # ops.flash_attention (`attn.schedule`'s residual_bytes)
            "names": list(REMAT_SAVED_NAMES),
            "layers": {group: n for group, (n, _) in groups.items()},
            "bytes_per_layer": {
                group: sizes for group, (_, sizes) in groups.items()
            },
            "head_chunk_rows": chunk,
            "head_chunks": -(-rows // chunk),
            "logits_bytes_avoided": rows * vocab * 4,
        },
    )


# Partition rules (stacked layout: dim 0 is the layer axis — never shard).
PARTITION_RULES = (
    (r"layers/w[qkv]$", P(None, "fsdp", "tp")),
    (r"layers/wo$", P(None, "tp", "fsdp")),
    (r"layers/w_(gate|up)$", P(None, "fsdp", "tp")),
    (r"layers/w_down$", P(None, "tp", "fsdp")),
    (r"^embed$", P("tp", "fsdp")),
    (r"^lm_head$", P("fsdp", "tp")),
)


def _adam_update(params, grads, opt, lr, b1, b2, eps):
    """Adam step; arithmetic in float32 regardless of the storage dtype
    (params/moments may be bfloat16 — see ``LlamaConfig.param_dtype``)."""
    count, m, v = opt
    count = count + 1
    f32 = jnp.float32
    m = jax.tree_util.tree_map(
        lambda m_, g: (b1 * m_.astype(f32) + (1 - b1) * g.astype(f32)).astype(
            m_.dtype
        ),
        m,
        grads,
    )
    v = jax.tree_util.tree_map(
        lambda v_, g: (
            b2 * v_.astype(f32) + (1 - b2) * g.astype(f32) ** 2
        ).astype(v_.dtype),
        v,
        grads,
    )
    mhat_scale = 1.0 / (1 - b1**count)
    vhat_scale = 1.0 / (1 - b2**count)
    params = jax.tree_util.tree_map(
        lambda p, m_, v_: (
            p.astype(f32)
            - lr
            * (m_.astype(f32) * mhat_scale)
            / (jnp.sqrt(v_.astype(f32) * vhat_scale) + eps)
        ).astype(p.dtype),
        params,
        m,
        v,
    )
    return params, (count, m, v)


def adam_part(lr, b1, b2, eps) -> Callable:
    """``(params, grads, opt) -> (params, opt)``: :func:`_adam_update`
    with its constants closed over (Python floats, as every step has
    folded them), as the step's ``optim.adam`` part."""
    return step_part(
        ADAM_SCOPE,
        lambda params, grads, opt: _adam_update(
            params, grads, opt, lr, b1, b2, eps
        ),
    )


def make_lora_train_step(
    config: LlamaConfig,
    lr: float = 1e-4,
    *,
    attn_fn: Callable = dot_product_attention,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    donate: bool = False,
):
    """Adam train step over **LoRA params only** (base weights frozen).

    Signature: (lora, opt, base_params, ids) → (lora, opt, loss); the
    next-token targets are ``ids`` shifted left.  ``opt`` = (step, m, v)
    from :func:`init_adam`.

    ``donate`` is opt-in: in a federated fine-tune the incoming adapters
    are also serialized for cross-party pushes, and donation would
    delete those buffers out from under the transport.
    """

    loss_fn = functools.partial(lora_loss, config=config, attn_fn=attn_fn)
    adam = adam_part(lr, b1, b2, eps)

    def llama_lora_step(lora, opt, base_params, ids):
        loss, grads = jax.value_and_grad(loss_fn)(lora, base_params, ids)
        lora, opt = adam(lora, grads, opt)
        return lora, opt, loss

    return jax.jit(llama_lora_step, donate_argnums=(0, 1) if donate else ())


def make_train_step(
    config: LlamaConfig,
    lr: float = 3e-4,
    *,
    attn_fn: Callable = dot_product_attention,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
):
    """Full-parameter Adam train step: (params, opt, ids) → (params, opt, loss).

    Params and both Adam moments are donated — the step runs in place in
    HBM, which is what lets the whole optimizer state stay device-resident
    between steps (no host round-trips in the training loop).
    """

    def loss_fn(params, ids):
        logits = apply_llama(params, ids, config, attn_fn=attn_fn)
        return lm_loss(logits[:, :-1], ids[:, 1:])

    def step_fn(params, opt, ids):
        loss, grads = jax.value_and_grad(loss_fn)(params, ids)
        params, opt = _adam_update(params, grads, opt, lr, b1, b2, eps)
        return params, opt, loss

    return jax.jit(step_fn, donate_argnums=(0, 1))


def make_train_loop(
    config: LlamaConfig,
    num_steps: int,
    lr: float = 3e-4,
    *,
    attn_fn: Callable = dot_product_attention,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
):
    """N full-param Adam steps in ONE compiled program (lax.scan).

    (params, opt, ids) → (params, opt, losses[num_steps]).  One dispatch
    covers all N steps, which keeps per-call dispatch out of a
    throughput measurement.
    """

    def loss_fn(params, ids):
        logits = apply_llama(params, ids, config, attn_fn=attn_fn)
        return lm_loss(logits[:, :-1], ids[:, 1:])

    def run(params, opt, ids):
        def body(carry, _):
            params, opt = carry
            loss, grads = jax.value_and_grad(loss_fn)(params, ids)
            params, opt = _adam_update(params, grads, opt, lr, b1, b2, eps)
            return (params, opt), loss

        (params, opt), losses = jax.lax.scan(
            body, (params, opt), None, length=num_steps
        )
        return params, opt, losses

    return jax.jit(run, donate_argnums=(0, 1))


def make_lora_train_loop(
    config: LlamaConfig,
    num_steps: int,
    lr: float = 1e-4,
    *,
    attn_fn: Callable = dot_product_attention,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
):
    """N LoRA Adam steps in ONE compiled program (lax.scan).

    (lora, opt, base_params, ids) → (lora, opt, losses[num_steps]); base
    stays frozen (may be int8-quantized, see :func:`quantize_llama_base`).
    Same one-dispatch rationale as :func:`make_train_loop`.
    """

    loss_fn = functools.partial(lora_loss, config=config, attn_fn=attn_fn)

    def run(lora, opt, base_params, ids):
        def body(carry, _):
            lora, opt = carry
            loss, grads = jax.value_and_grad(loss_fn)(lora, base_params, ids)
            lora, opt = _adam_update(lora, grads, opt, lr, b1, b2, eps)
            return (lora, opt), loss

        (lora, opt), losses = jax.lax.scan(
            body, (lora, opt), None, length=num_steps
        )
        return lora, opt, losses

    return jax.jit(run, donate_argnums=(0, 1))


def param_count(params: Params, *, exclude_embed: bool = False) -> int:
    """Total parameter count (optionally excluding the embedding table).

    Works on real arrays or ``jax.eval_shape`` abstract values — use
    ``param_count(jax.eval_shape(lambda: init_llama(k, cfg)))`` to count
    without allocating.
    """
    import math

    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        name = jax.tree_util.keystr(path)
        if exclude_embed and "embed" in name:
            continue
        total += math.prod(leaf.shape)
    return total


def init_adam(params: Params):
    """Adam state (step, m, v).

    ``v`` is float32 regardless of the param storage dtype: with
    b2=0.999 the 0.1% per-step EMA change is under half a bf16 ulp, so a
    bfloat16 second moment could grow but never decay (the cast back
    rounds to the unchanged value).  ``m`` follows the param dtype — its
    b1=0.9 EMA moves ~10% per step, far above bf16 rounding, and keeping
    it narrow is part of fitting 1B params + Adam on one 16 GB chip.
    """
    zeros = functools.partial(jax.tree_util.tree_map, jnp.zeros_like)
    zeros32 = functools.partial(
        jax.tree_util.tree_map, lambda p: jnp.zeros(p.shape, jnp.float32)
    )
    return (jnp.zeros((), jnp.int32), zeros(params), zeros32(params))
