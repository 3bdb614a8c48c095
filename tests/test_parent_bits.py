"""The LoRA steps of the toy models that were there before the decoder
took a layer that is not attention (PR 35): Trinity's (window and full
attention under a ``cond``, expert layers), Kimi's (latent attention)
and Mistral's (``llama.py``), bf16 through the flash kernels and the
checkpointed scan, ``lora_loss`` and every adapter leaf's gradient; and
those of the two users of ``ops/ssd.py`` and of the attention without
positions: Granite's (one group, the block's multipliers, the tied head)
and Nemotron's (eight groups, latent experts, the MTP module), pinned
before the decoder took block-sparse and linear attention.

Loss (as ``float.hex``) and a digest of the gradients' bytes on the
CPU: groups that split by the mixer's kind, the block's multipliers and
the tied head default to what those blocks were before the decoder took
such a layer, and their programs may not change.

Two builds are pinned (``BITS``).  ``default`` is what ships and what
the benchmark runs: XLA may hand a fusion's consumer the float32 value a
bf16 result was rounded from, so WHICH values are rounded follows the
compiler's fusions, and those move with what a checkpoint keeps.  PR 38
keeps the stream between a layer's sub-blocks (``layer.mid``), and JAX
puts a ``reduce_precision`` on a kept residual's producer: the FFN's
norm now reads the rounded stream, and this build's bits are
re-recorded from PR 38's tree (they were PR 35's parent's, 5c5c886,
until then; the losses moved by 1e-4 to 4e-4 on these toys).
``strict`` is the same program compiled with
``xla_allow_excess_precision`` off, where every bf16 result is rounded
before its consumer: the parent of PR 38 (0616888) and its tree compute
the same bits there, loss and every gradient byte, which shows that the
program JAX states did not change.  Because a strict build cannot see a
rounding that MOVES (a kept bf16 array where a float32 one was read
passes it), the default build is also held to a float32 run of the same
toy (``FLOAT32_BOUNDS``): the pin says that a bit moved, the bound
whether the build got further from the exact gradients.
"""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from rayfed_tpu.models import decoder, llama, lora, moe
from rayfed_tpu.ops.flash_attention import flash_attention
from tests.test_kimi_k2 import STRICT, _trained, make, toy_config

BITS = {
    "default": {
        "trinity": ("0x1.352b9e0000000p+2", "10e86cfe54656515"),
        "kimi": ("0x1.0d45040000000p+2", "337bc6de6b201a6c"),
        "mistral": ("0x1.8bfc1e0000000p+2", "d49e9d9423052a21"),
        "granite": ("0x1.0a61ee0000000p+2", "1213abb9ffa5b975"),
        "nemotron": ("0x1.57a63c0000000p+2", "059a8de0f37e1b13"),
    },
    # single-threaded contractions and XLA's own elementary functions
    # too (`test_kimi_k2.STRICT` says why)
    "strict": {
        "trinity": ("0x1.35605a0000000p+2", "928c5b38516f8abf"),
        "kimi": ("0x1.0d7bdc0000000p+2", "e3e2c38c95b8580b"),
        "mistral": ("0x1.8bfd5c0000000p+2", "7fed9ece5506e9a8"),
        "granite": ("0x1.0a61ae0000000p+2", "235f886186b4b5b1"),
        "nemotron": ("0x1.57a63c0000000p+2", "3c3912d35972ddec"),
    },
}
BUILDS = {"default": {}, "strict": STRICT}
# model -> bounds on the default bf16 build's distance from the same toy
# in float32 (the loss relative; all adapter gradients together and the
# worst leaf, each as |got - want| / |want|): a tenth above what PR 38's
# tree reads, 3.6e-4 / 4.18% / 8.45% Trinity's, 3.2e-4 / 6.11% / 16.3%
# Kimi's, 3.0e-4 / 1.77% / 2.50% Mistral's.  Its parent read 1.5e-5 /
# 4.41% / 7.38%, 1.2e-3 / 14.3% / 65.0% and 3.1e-5 / 1.69% / 2.29%, and
# the strict build reads 1.0e-3 / 4.99% / 8.42%, 1.1e-3 / 5.48% / 17.0%,
# 3.0e-4 / 1.78% / 2.60%: the toys' bf16 noise, in which keeping the
# stream moved the three both ways.
FLOAT32_BOUNDS = {
    "trinity": (4.0e-4, 0.046, 0.093),
    "kimi": (3.6e-4, 0.0672, 0.18),
    "mistral": (3.3e-4, 0.0195, 0.0275),
    # a tenth above what the tree at 493da76 reads: 6.3e-6 / 2.45% / 3.53%
    # Granite's, 1.44e-3 / 8.66% / 26.5% Nemotron's (eleven blocks and
    # the MTP module of bf16)
    "granite": (7.0e-6, 0.027, 0.039),
    "nemotron": (1.6e-3, 0.096, 0.292),
}


def _bits(loss, grads):
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(grads):
        h.update(np.asarray(leaf).tobytes())
    return float(loss).hex(), h.hexdigest()[:16]


def _trinity(dtype=jnp.bfloat16):
    experts = moe.ExpertShareConfig(
        num_experts=8, held=(0, 1, 2, 3), top_k=3, d_model=32, d_ff=16,
        route_scale=2.826,
    )
    cfg = decoder.DecoderConfig(
        layers=(decoder.LayerSpec("window", "dense"),
                decoder.LayerSpec("window", "moe"),
                decoder.LayerSpec("full", "moe")),
        vocab_size=64, hidden_size=32, num_heads=4, num_kv_heads=2,
        head_dim=8, intermediate_size=48, sliding_window=8,
        embed_scale=32 ** 0.5, experts=experts, dtype=dtype,
        param_dtype=jnp.float32, remat=True,
    )
    base = decoder.init_decoder(jax.random.PRNGKey(0), cfg)
    adapters = _trained(lora.init_lora(
        jax.random.PRNGKey(1), base, lora.LoraConfig(
            rank=2, alpha=4.0,
            targets=(r"/w[qkvoz]$", r"/w_(gate|up|down)$"),
        )), 2)
    ids = jax.random.randint(jax.random.PRNGKey(3), (1, 24), 0, 64)
    return adapters, lambda a: decoder.lora_loss(
        a, base, ids, cfg, attn_fn=flash_attention
    )[0]


def _kimi(dtype=jnp.bfloat16):
    cfg, base, adapters, ids = make(cfg=toy_config(dtype=dtype, remat=True))
    return adapters, lambda a: decoder.lora_loss(
        a, base, ids, cfg, attn_fn=flash_attention
    )[0]


def _mistral(dtype=jnp.bfloat16):
    cfg = llama.llama_tiny(sliding_window=16, remat=True, dtype=dtype)
    base = llama.init_llama(jax.random.PRNGKey(0), cfg)
    adapters = _trained(lora.init_lora(
        jax.random.PRNGKey(1), base, lora.LoraConfig(rank=2, alpha=4.0)
    ), 2)
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, 48), 0, 256)
    return adapters, lambda a: llama.lora_loss(
        a, base, ids, cfg, attn_fn=flash_attention
    )


def _granite(dtype=jnp.bfloat16):
    from tests import test_granite_hybrid as granite

    cfg, base, adapters, ids = granite.make(
        cfg=granite.toy_config(dtype=dtype, remat=True)
    )
    return adapters, lambda a: decoder.lora_loss(
        a, base, ids, cfg, attn_fn=flash_attention
    )[0]


def _nemotron(dtype=jnp.bfloat16):
    from tests import test_nemotron_h as nemotron

    cfg, base, adapters, ids = nemotron.make(
        cfg=nemotron.toy_config(dtype=dtype, remat=True)
    )
    return adapters, lambda a: decoder.lora_loss(
        a, base, ids, cfg, attn_fn=flash_attention
    )[0]


MODELS = {"trinity": _trinity, "kimi": _kimi, "mistral": _mistral,
          "granite": _granite, "nemotron": _nemotron}


@pytest.mark.parametrize("build", list(BITS))
@pytest.mark.parametrize("model", list(MODELS))
def test_the_lora_steps_that_were_there_compute_the_parents_bits(model, build):
    adapters, loss_fn = MODELS[model]()
    loss, grads = jax.jit(
        jax.value_and_grad(loss_fn), **BUILDS[build]
    )(adapters)
    assert _bits(loss, grads) == BITS[build][model]


@pytest.mark.parametrize("model", list(MODELS))
def test_the_default_build_stays_as_close_to_float32(model):
    """What ships against the exact gradients: the same toy and seed in
    float32 is the reference, and the default bf16 build's loss, its
    adapter gradients together and its worst leaf stay inside
    ``FLOAT32_BOUNDS``.  A rounding that moves (a value kept in bf16
    where the float32 it was rounded from was read) changes the pinned
    bits above; this says whether the step got worse by it."""
    adapters, exact_fn = MODELS[model](jnp.float32)
    exact, exact_grads = jax.jit(jax.value_and_grad(exact_fn))(adapters)
    adapters, loss_fn = MODELS[model]()
    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(adapters)
    loss_bound, all_bound, leaf_bound = FLOAT32_BOUNDS[model]
    assert abs(float(loss) - float(exact)) <= loss_bound * abs(float(exact))
    off = norm = 0.0
    for want, got in zip(jax.tree_util.tree_leaves(exact_grads),
                         jax.tree_util.tree_leaves(grads)):
        want, got = np.asarray(want, np.float64), np.asarray(got, np.float64)
        assert np.linalg.norm(got - want) <= leaf_bound * np.linalg.norm(want)
        off, norm = off + np.sum((got - want) ** 2), norm + np.sum(want ** 2)
    assert off <= all_bound ** 2 * norm
