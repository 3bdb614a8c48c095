"""The LoRA steps of the toy models that were there before the decoder
took a layer that is not attention (PR 35): Trinity's (window and full
attention under a ``cond``, expert layers), Kimi's (latent attention)
and Mistral's (``llama.py``), bf16 through the flash kernels and the
checkpointed scan, ``lora_loss`` and every adapter leaf's gradient.

Loss (as ``float.hex``) and a digest of the gradients' bytes, recorded
on the PARENT of PR 35 (commit 5c5c886) on the CPU before
``decoder.py`` was touched: groups that split by the mixer's kind, the
block's multipliers and the tied head default to what those blocks
were, and their programs may not change.
"""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from rayfed_tpu.models import decoder, llama, lora, moe
from rayfed_tpu.ops.flash_attention import flash_attention
from tests.test_kimi_k2 import _trained, make, toy_config

PARENT_BITS = {
    "trinity": ("0x1.3510460000000p+2", "79d25faf0b74abb8"),
    "kimi": ("0x1.0d83d00000000p+2", "2f7fd917400f0776"),
    "mistral": ("0x1.8c1d440000000p+2", "8a16b99d955cccff"),
}


def _bits(loss, grads):
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(grads):
        h.update(np.asarray(leaf).tobytes())
    return float(loss).hex(), h.hexdigest()[:16]


def _trinity():
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
        embed_scale=32 ** 0.5, experts=experts, dtype=jnp.bfloat16,
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


def _kimi():
    cfg, base, adapters, ids = make(
        cfg=toy_config(dtype=jnp.bfloat16, remat=True)
    )
    return adapters, lambda a: decoder.lora_loss(
        a, base, ids, cfg, attn_fn=flash_attention
    )[0]


def _mistral():
    cfg = llama.llama_tiny(sliding_window=16, remat=True, dtype=jnp.bfloat16)
    base = llama.init_llama(jax.random.PRNGKey(0), cfg)
    adapters = _trained(lora.init_lora(
        jax.random.PRNGKey(1), base, lora.LoraConfig(rank=2, alpha=4.0)
    ), 2)
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, 48), 0, 256)
    return adapters, lambda a: llama.lora_loss(
        a, base, ids, cfg, attn_fn=flash_attention
    )


MODELS = {"trinity": _trinity, "kimi": _kimi, "mistral": _mistral}


@pytest.mark.parametrize("model", list(PARENT_BITS))
def test_the_lora_steps_that_were_there_compute_the_parents_bits(model):
    adapters, loss_fn = MODELS[model]()
    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(adapters)
    assert _bits(loss, grads) == PARENT_BITS[model]
