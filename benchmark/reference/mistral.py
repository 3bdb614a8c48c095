"""Plain reference forward of the Mistral architecture (arXiv:2310.06825).

Straightforward ``jax.numpy`` in float32: RMSNorm, rotary embeddings
on interleaved pairs (the layout ``rayfed_tpu.models.llama`` stores its
weights for), grouped-query attention under a banded causal mask (query
``t`` sees keys in ``(t - window, t]``), SwiGLU, untied head.  No
kernels, no cache, no remat; attention is computed in query blocks so
that 8,192 positions fit.  Callers wrap it in
``jax.default_matmul_precision("highest")``: on a TPU a float32 matmul
otherwise runs in lower precision.

Takes the system's own parameter tree (stacked layers, any float dtype)
so both sides read the same weights.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x, scale, eps):
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


def _banded_attention(q, k, v, window, block):
    """q: [T, H, Dh], k/v: [T, H, Dh] (kv heads already repeated)."""
    t, h, dh = q.shape
    scale = dh ** -0.5
    key_pos = jnp.arange(t)

    def one_block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block, 0)
        q_pos = i * block + jnp.arange(block)
        s = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        seen = key_pos[None, :] <= q_pos[:, None]
        if window is not None:
            seen &= key_pos[None, :] > q_pos[:, None] - window
        s = jnp.where(seen[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(one_block, jnp.arange(t // block))
    return out.reshape(t, h, dh)


def forward_logits(params, ids, *, num_layers, num_heads, num_kv_heads,
                   rope_theta, rms_eps, window, last, block=512):
    """Logits ``[last, vocab]`` of the final ``last`` positions of ONE
    sequence ``ids`` ([T]) through the first ``num_layers`` layers of
    ``params``, the final norm and the head."""
    f = lambda a: jnp.asarray(a, F32)
    x = f(params["embed"])[ids]
    t, d = x.shape
    dh = d // num_heads
    block = min(block, t)
    assert t % block == 0, (t, block)
    lp = params["layers"]
    for i in range(num_layers):
        y = _rms_norm(x, f(lp["attn_norm"][i]), rms_eps)
        q = (y @ f(lp["wq"][i])).reshape(t, num_heads, dh)
        k = (y @ f(lp["wk"][i])).reshape(t, num_kv_heads, dh)
        v = (y @ f(lp["wv"][i])).reshape(t, num_kv_heads, dh)
        q, k = _rope(q, rope_theta), _rope(k, rope_theta)
        reps = num_heads // num_kv_heads
        k, v = jnp.repeat(k, reps, axis=1), jnp.repeat(v, reps, axis=1)
        a = _banded_attention(q, k, v, window, block)
        x = x + a.reshape(t, d) @ f(lp["wo"][i])
        y = _rms_norm(x, f(lp["mlp_norm"][i]), rms_eps)
        gate = jax.nn.silu(y @ f(lp["w_gate"][i]))
        x = x + (gate * (y @ f(lp["w_up"][i]))) @ f(lp["w_down"][i])
    x = _rms_norm(x[-last:], f(params["final_norm"]), rms_eps)
    head = params.get("lm_head")
    head = f(params["embed"]).T if head is None else f(head)
    return x @ head
