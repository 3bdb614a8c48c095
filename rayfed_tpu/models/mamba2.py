"""The Mamba-2 mixer: what stands where attention stands in a layer of
:mod:`rayfed_tpu.models.decoder` whose :class:`~decoder.LayerSpec` says
``"ssm"`` (transformers ``models/granitemoehybrid/
modeling_granitemoehybrid.py``: ``GraniteMoeHybridMambaLayer``,
``GraniteMoeHybridRMSNormGated``; arXiv:2405.21060).

The contract, on the normed stream ``y`` [B, T, D] with ``H`` heads of
width ``P``, a state of ``N`` a head, ``G`` groups, ``d_inner = H P``:

- ``[z | xBC | dt] = y W_in`` (``d_inner | d_inner + 2 G N | H``, in the
  published order: gate, convolved part, time step);
- ``xBC <- silu(b + sum_j w[:, j] xBC_{t - (K - 1) + j})``, zeros before
  position 0: a causal depthwise convolution of width ``K`` with bias;
- ``[x | B | C] = xBC`` (``d_inner`` as ``[H, P]`` | ``G N`` | ``G N``);
  ``dt <- softplus(dt + dt_bias)``; ``A = -exp(A_log)``;
- ``y = ssd_scan(x, dt, A, B, C, D)`` (:mod:`rayfed_tpu.ops.ssd`);
- ``u = y * silu(z)``; ``o = u / sqrt(mean(u^2) + eps) * ssm_norm`` (the
  gate INSIDE the norm; the mean over each group's ``d_inner / G``
  channels: ``nemotron_h``'s ``MambaRMSNormGated``, and over all of
  ``d_inner`` where ``G`` is 1, as granitemoehybrid's);
- ``mixer(y) = o W_out``.

Parameters: ``w_in``, ``w_out`` (through ``llama._linear``, so a LoRA
entry applies as to every other matrix), ``conv_w`` [C, K], ``conv_b``
[C], ``A_log``, ``D``, ``dt_bias`` [H] (float32 whatever the parameter
type, as the published code keeps them), ``ssm_norm`` [d_inner].
Scopes on a device trace: ``ssm.proj`` (both projections and the gated
norm), ``ssm.conv``, ``ssm.scan``.  ``W_in``'s product carries the
checkpoint name ``ssm.in`` (``llama.SSM_IN_NAME``), which a
rematerialized layer's policy keeps (``llama.REMAT_SAVED``): the second
forward slices gate, convolved part and time step from the kept array
and runs neither ``W_in`` nor its adapter's ``(y a) b`` again; the
convolution, the scan and the gated norm it does run again (their
backward reads float32 values that are not kept).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from rayfed_tpu.models.llama import SSM_IN_NAME, _linear
from rayfed_tpu.ops.ssd import ssd_scan

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class SsmConfig:
    """The widths of a state-space layer: heads and their width, the
    state a head carries, the groups that share ``B`` and ``C``, the
    convolution's taps and the scan's chunk."""

    num_heads: int = 64
    head_dim: int = 64
    state: int = 128
    groups: int = 1
    conv_width: int = 4
    chunk: int = 256

    def __post_init__(self):
        if self.num_heads % self.groups:
            raise ValueError("ssm groups must divide ssm heads")

    @property
    def d_inner(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.groups * self.state

    @property
    def proj_dim(self) -> int:
        return self.d_inner + self.conv_dim + self.num_heads


def init_mixer(key: jax.Array, hidden: int, config: SsmConfig, pdt) -> Params:
    """Random weights as the Mamba-2 reference initialises them
    (state-spaces/mamba ``modules/mamba2.py``): ``A`` uniform in [1, 16],
    ``dt_bias`` the inverse softplus of a ``dt`` log-uniform in [0.001,
    0.1], ``D`` and the gated norm at one, the convolution and its bias
    uniform in ``+-K ** -0.5``; the two matrices normal ``fan_in **
    -0.5`` as the decoder's others."""
    m = config
    ks = jax.random.split(key, 6)
    bound = m.conv_width ** -0.5
    dt = jnp.exp(jax.random.uniform(
        ks[2], (m.num_heads,), minval=math.log(1e-3), maxval=math.log(1e-1)
    ))
    return {
        "w_in": (jax.random.normal(ks[0], (hidden, m.proj_dim))
                 * hidden ** -0.5).astype(pdt),
        "w_out": (jax.random.normal(ks[1], (m.d_inner, hidden))
                  * m.d_inner ** -0.5).astype(pdt),
        "conv_w": jax.random.uniform(
            ks[3], (m.conv_dim, m.conv_width), minval=-bound, maxval=bound
        ).astype(pdt),
        "conv_b": jax.random.uniform(
            ks[4], (m.conv_dim,), minval=-bound, maxval=bound
        ).astype(pdt),
        # softplus(dt_bias) = dt
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(
            ks[5], (m.num_heads,), minval=1.0, maxval=16.0
        )),
        "D": jnp.ones((m.num_heads,), jnp.float32),
        "ssm_norm": jnp.ones((m.d_inner,), pdt),
    }


def causal_conv(xbc, w, bias):
    """``silu(bias + sum_j w[:, j] xbc[t - (K - 1) + j])`` along the
    tokens of ``xbc`` [B, T, C], zeros before position 0; float32
    arithmetic, ``xbc``'s type out."""
    k = w.shape[1]
    t = xbc.shape[1]
    padded = jnp.pad(xbc.astype(jnp.float32), [(0, 0), (k - 1, 0), (0, 0)])
    w = w.astype(jnp.float32)
    out = bias.astype(jnp.float32)
    for j in range(k):
        out = out + padded[:, j:j + t] * w[:, j]
    return jax.nn.silu(out).astype(xbc.dtype)


def gated_norm(y, z, scale, eps, groups: int = 1):
    """``rms_norm(y * silu(z)) * scale`` over the last dim, float32; with
    ``groups`` each of that many equal parts of the last dim normed
    alone."""
    u = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    if groups == 1:
        u = u * jax.lax.rsqrt(jnp.mean(u * u, axis=-1, keepdims=True) + eps)
    else:
        parts = u.reshape(*u.shape[:-1], groups, -1)
        parts = parts * jax.lax.rsqrt(
            jnp.mean(parts * parts, axis=-1, keepdims=True) + eps
        )
        u = parts.reshape(u.shape)
    return (u * scale.astype(jnp.float32)).astype(y.dtype)


def apply_mixer(y, lp: Params, config: SsmConfig, lget, dtype, eps):
    """The mixer on the normed stream ``y`` [B, T, D] -> [B, T, D].
    ``lp`` is ONE layer's entries, ``lget(name)`` its LoRA entry or
    None."""
    m = config
    b, t, _ = y.shape
    gn = m.groups * m.state
    with jax.named_scope("ssm.proj"):
        proj = _linear(y, lp["w_in"], lget("w_in"), dtype)
        proj = checkpoint_name(proj, SSM_IN_NAME)
        z = proj[..., : m.d_inner]
        xbc = proj[..., m.d_inner: m.d_inner + m.conv_dim]
        dt = jax.nn.softplus(
            proj[..., m.d_inner + m.conv_dim:].astype(jnp.float32)
            + lp["dt_bias"].astype(jnp.float32)
        )
    with jax.named_scope("ssm.conv"):
        xbc = causal_conv(xbc, lp["conv_w"], lp["conv_b"])
    x = xbc[..., : m.d_inner].reshape(b, t, m.num_heads, m.head_dim)
    B = xbc[..., m.d_inner: m.d_inner + gn].reshape(b, t, m.groups, m.state)
    C = xbc[..., m.d_inner + gn:].reshape(b, t, m.groups, m.state)
    A = -jnp.exp(lp["A_log"].astype(jnp.float32))
    s = ssd_scan(x, dt, A, B, C, lp["D"], chunk=m.chunk)
    with jax.named_scope("ssm.proj"):
        o = gated_norm(s.reshape(b, t, m.d_inner), z, lp["ssm_norm"], eps,
                       m.groups)
        return _linear(o, lp["w_out"], lget("w_out"), dtype)
